"""A whole run of the harness on the CPU, at a tiny size, for cells
defined only by files in a temporary checkout: a sound run comes out
correct, and each fault planted in the timed path, and the
lower-precision control, comes out not correct."""
import json
import os
import shutil

import _bench_path  # noqa: F401
import numpy as np
import pytest

from benchlib import decode_work, harness, serve, spec, trace

TINY = {
    "name": "tiny", "num_hidden_layers": 2, "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
    "sliding_window": 32, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "reference": "llama",
    # set from readings at this size on the CPU, two seeds: the program
    # read 0 and 4.2e-5 (logit_gap) and 0 (layer_rel_err), the float8
    # control 0.084 and 0.17, and 0.048; a planted fault reads over 0.1
    "limits": {"logit_gap": 0.01, "layer_rel_err": 1e-4}}
CHAT = {"kind": "serve", "arrival": "poisson", "rate_rps": 8,
        "capacity": 4, "max_context": 96, "page_size": 16,
        "prefill_per_step": 1,
        "prompt": {"median": 24, "sigma": 0.5, "min": 8, "max": 48,
                   "buckets": [16, 32, 48]},
        "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
        "draw_seed": 5, "check_requests": 4}
GRAPH = {"kind": "graph", "l": 64, "dtype": "float32", "inputs": 2,
         "reference": "single_head_layer"}
SEED = 2 ** 31 + 12345


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout holding only its own BENCHMARK.json, configuration,
    mixes, metric readers, references and architectures."""
    r = tmp_path_factory.mktemp("checkout")
    for d in ("metrics", "references", "architectures"):
        shutil.copytree(os.path.join(_bench_path.BENCH, d), r / "bench" / d)
    (r / "bench" / "configs").mkdir()
    (r / "bench" / "traffic").mkdir()
    (r / "bench" / "configs" / "tiny.json").write_text(json.dumps(TINY))
    (r / "bench" / "traffic" / "tiny_chat.json").write_text(json.dumps(CHAT))
    (r / "bench" / "traffic" / "tiny_graph.json").write_text(
        json.dumps(GRAPH))
    (r / "bench" / "metrics" / "served_requests.py").write_text(
        "def read(run):\n    return float(len(run.records))\n")
    with open(os.path.join(_bench_path.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "tiny", "source": "test", "reduced": [],
                         "file": "bench/configs/tiny.json", "why": "test"}]
    bench["workloads"] = [
        {"name": "tiny.chat", "config": "tiny", "traffic": "tiny_chat",
         "chips": 1, "why": "test"},
        {"name": "tiny.graph", "config": "tiny", "traffic": "tiny_graph",
         "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            graph_only = m["workloads"] == ["danube.layer_graph"]
            m["workloads"] = ["tiny.graph" if graph_only else "tiny.chat"]
    bench["end_to_end"].append(
        {"name": "served_requests", "unit": "requests", "better": "higher",
         "bound": 0.01, "source": "host_clock", "workloads": ["tiny.chat"]})
    (r / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(r)


def test_cell_is_found_by_name(root):
    cell = spec.load_cell(root, "tiny.chat")
    assert cell.config["hidden_size"] == 64
    assert cell.traffic["capacity"] == 4
    names = [m.name for m in cell.end_to_end]
    assert names == ["tokens_per_s", "tpot_p90_ms", "setup_s",
                     "served_requests"]
    assert "ttft_p90_ms.chat" in [m.name for m in cell.per_layer]
    assert "gen_roofline" not in [m.name for m in cell.per_layer]
    assert cell.reference().__name__ == "bench_llama"
    assert spec.load_cell(root, "tiny.graph").reference().__name__ == \
        "bench_single_head_layer"
    with pytest.raises(KeyError):
        spec.load_cell(root, "danube.chat")


def run(root, name):
    return harness.run_cell(root, name, SEED, 2.0, False)


def test_sound_serve_run_is_correct(root):
    line = run(root, "tiny.chat")
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 5
    assert list(line)[-1] == "checks"
    m = line["metrics"]
    assert set(m) == {"tokens_per_s", "tpot_p90_ms", "setup_s",
                      "served_requests"}
    assert m["served_requests"]["value"] == line["attempted"]
    assert line["device"]["platform"] == "cpu"


def test_token_altered_where_produced_is_caught(root, monkeypatch):
    import dataclasses

    from repro.serve.slots import SlotEngine

    step = SlotEngine.step

    def altered(self):
        res = step(self)
        data = res.data.copy()
        data[:, 0] = (data[:, 0] + 1) % self.cfg.vocab
        return dataclasses.replace(res, data=data)

    monkeypatch.setattr(SlotEngine, "step", altered)
    line = run(root, "tiny.chat")
    assert not line["correct"]
    assert line["checks"]["logit_gap"]["value"] > 0.1


def test_step_returning_its_cache_unchanged_is_caught(root, monkeypatch):
    from repro.serve.pages import PageLayout

    monkeypatch.setattr(PageLayout, "scatter_written",
                        lambda self, pools, *a: dict(pools))
    line = run(root, "tiny.chat")
    assert not line["correct"]
    assert line["checks"]["logit_gap"]["value"] > 0.1


def test_sound_graph_run_is_correct(root):
    line = run(root, "tiny.graph")
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"gen_tflop_per_s", "setup_s"}


def test_answer_altered_where_produced_is_caught(root, monkeypatch):
    from repro.graph.executor import GraphAccelerator

    call = GraphAccelerator.__call__
    monkeypatch.setattr(GraphAccelerator, "__call__",
                        lambda self, ops: call(self, ops).at[0].multiply(-1))
    line = run(root, "tiny.graph")
    assert not line["correct"]


def test_float8_control_fails_the_limits(root, capsys):
    """The plain reference in float8 put in the program's place reads
    above each check's limit; the program reads below it."""
    import calibrate

    for name, limit in (("tiny.chat", "logit_gap"),
                        ("tiny.graph", "layer_rel_err")):
        cell = spec.load_cell(root, name)
        calibrate.limits(cell, [SEED, 7], 2.0)
        rows = [json.loads(ln) for ln in
                capsys.readouterr().out.strip().splitlines()]
        bound = TINY["limits"][limit]
        assert all(r["program"] <= bound < r["control"] for r in rows), rows
        assert np.all([r["number"] == limit for r in rows])


#: an architecture the repository does not have: a tiny mixture of
#: experts, mapped onto the program's existing ``family="moe"``, with
#: counts of its own
MOE_ARCHITECTURE = """
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class MoeDims:
    experts: int
    top_k: int


def program_config(conf):
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=conf["name"], family="moe",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        head_dim=conf["head_dim"], n_experts=conf["num_local_experts"],
        top_k=conf["num_experts_per_tok"],
        tie_embeddings=conf["tie_word_embeddings"],
        dtype=conf["torch_dtype"])


def dims(conf):
    return MoeDims(conf["num_local_experts"], conf["num_experts_per_tok"])


def decode_needs(dims, steps, contexts):
    return len(list(contexts)) * dims.top_k, steps * dims.experts


def init_scale(path, leaf):
    return 1.0 if getattr(path[-1], "key", "") == "router" else None
"""


def checkout_with(root, tmp_path, conf):
    """A copy of the ``root`` checkout with one more configuration and a
    cell serving it the tiny chat mix: new files and entries only."""
    r = tmp_path / "checkout"
    shutil.copytree(root, r)
    (r / "bench" / "configs" / f"{conf['name']}.json").write_text(
        json.dumps(conf))
    bench = json.loads((r / "BENCHMARK.json").read_text())
    bench["configs"].append(
        {"name": conf["name"], "source": "test", "reduced": [],
         "file": f"bench/configs/{conf['name']}.json", "why": "test"})
    cell = conf["name"] + ".chat"
    bench["workloads"].append({"name": cell, "config": conf["name"],
                               "traffic": "tiny_chat", "chips": 1,
                               "why": "test"})
    (r / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(r), cell


def test_new_architecture_needs_only_new_files(root, tmp_path):
    conf = dict(TINY, name="tiny-moe", reference="tiny_moe",
                num_local_experts=4, num_experts_per_tok=2)
    r, name = checkout_with(root, tmp_path, conf)
    with open(os.path.join(r, "bench", "architectures", "tiny_moe.py"),
              "w") as f:
        f.write(MOE_ARCHITECTURE)
    cell = spec.load_cell(r, name)
    engine, server, params, requests = serve.setup(cell, SEED, 2.0)
    try:
        assert engine.cfg.family == "moe"
        assert (engine.cfg.n_experts, engine.cfg.top_k) == (4, 2)
        # the module's rule, N(0, 1), not the default's N(0, 1/64)
        router = np.asarray(params["layers"]["ffn"]["router"], np.float32)
        assert router.shape == (2, 64, 4)
        assert 0.8 < router.std() < 1.25
        records, t0, t1, _, _ = serve.window(server, requests, 2.0)
        records = serve.collect(records, t1, backlog=False)
    finally:
        serve.free(engine, server)
    assert len(records) > 5 and all(r.served for r in records)
    # the decode metrics count through the module too
    run = harness.Run(cell=cell, kind="serve", window_s=t1 - t0, peaks=None,
                      records=records)
    steps = [trace.Event(f"jit_step({i})", 10 * i, 10 * i + 5)
             for i in range(3)]
    run.trace = trace.Trace(window=(0, 100), host=[], devices=[
        trace.Device("/device:TPU:0", steps, [])])
    run.trace_host = (t0, t1 + serve.DRAIN_S)
    w = decode_work.decode_work(run)
    assert w.tokens > 0
    assert (w.flops, w.bytes) == (2 * w.tokens, 3 * 4)


def test_configuration_without_architecture_fails_at_load_cell(root,
                                                                tmp_path):
    r, name = checkout_with(root, tmp_path,
                            dict(TINY, name="tiny-x", reference="tiny_x"))
    with pytest.raises(FileNotFoundError,
                       match=r"bench/architectures/tiny_x\.py"):
        spec.load_cell(r, name)
