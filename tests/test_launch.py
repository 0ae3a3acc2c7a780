"""Launch-layer tests: HLO analyzer units + a miniature dry-run cell
(subprocess with 8 fake devices — the full 512-device sweep is
`python -m repro.launch.dryrun`, recorded in EXPERIMENTS.md)."""
import os
import subprocess
import sys
import textwrap


from repro.launch import hlo_analysis as H


class TestHloAnalysis:
    def test_shape_bytes(self):
        assert H.shape_bytes("f32[2,3]{1,0}") == 24
        assert H.shape_bytes("bf16[128]") == 256
        assert H.shape_bytes("(f32[2], s32[4])") == 24
        assert H.shape_bytes("pred[]") == 1

    def test_group_size_formats(self):
        assert H._group_size("replica_groups={{0,1,2,3},{4,5,6,7}}", 1) == 4
        assert H._group_size("replica_groups=[16,32]<=[512]", 1) == 32
        assert H._group_size("no groups here", 7) == 7

    def test_wire_factors(self):
        assert H._WIRE_FACTOR["all-gather"](160, 16) == 150
        assert H._WIRE_FACTOR["all-reduce"](160, 16) == 300
        assert H._WIRE_FACTOR["collective-permute"](160, 16) == 160

    def test_analyze_synthetic_module(self):
        hlo = textwrap.dedent("""\
        HloModule test

        %cond (p: (s32[], f32[8,8])) -> pred[] {
          %p = (s32[], f32[8,8]) parameter(0)
          %i = s32[] get-tuple-element(%p), index=0
          %n = s32[] constant(5)
          ROOT %lt = pred[] compare(%i, %n), direction=LT
        }

        %body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
          %p = (s32[], f32[8,8]) parameter(0)
          %x = f32[8,8] get-tuple-element(%p), index=1
          %d = f32[8,8] dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
          %ag = f32[8,8] all-gather(%d), replica_groups=[4,4]<=[16], dimensions={0}
          %i = s32[] get-tuple-element(%p), index=0
          %one = s32[] constant(1)
          %i2 = s32[] add(%i, %one)
          ROOT %t = (s32[], f32[8,8]) tuple(%i2, %ag)
        }

        ENTRY %main (a: f32[8,8]) -> f32[8,8] {
          %a = f32[8,8] parameter(0)
          %zero = s32[] constant(0)
          %t0 = (s32[], f32[8,8]) tuple(%zero, %a)
          %w = (s32[], f32[8,8]) while(%t0), condition=%cond, body=%body
          ROOT %out = f32[8,8] get-tuple-element(%w), index=1
        }
        """)
        st = H.analyze(hlo)
        # dot: 2*64*8 = 1024 flops, x5 trips
        assert st.flops == 1024 * 5
        # all-gather result 256B * 3/4 * 5 trips
        assert st.wire_bytes == 256 * 0.75 * 5
        assert st.trip_counts == {"w": 5}

    def test_nested_while_multiplies(self):
        hlo = textwrap.dedent("""\
        HloModule nested

        %icond (p: (s32[], f32[4,4])) -> pred[] {
          %p = (s32[], f32[4,4]) parameter(0)
          %i = s32[] get-tuple-element(%p), index=0
          %n = s32[] constant(3)
          ROOT %lt = pred[] compare(%i, %n), direction=LT
        }

        %ibody (p: (s32[], f32[4,4])) -> (s32[], f32[4,4]) {
          %p = (s32[], f32[4,4]) parameter(0)
          %x = f32[4,4] get-tuple-element(%p), index=1
          %d = f32[4,4] dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
          %i = s32[] get-tuple-element(%p), index=0
          %one = s32[] constant(1)
          %i2 = s32[] add(%i, %one)
          ROOT %t = (s32[], f32[4,4]) tuple(%i2, %d)
        }

        %ocond (p: (s32[], f32[4,4])) -> pred[] {
          %p = (s32[], f32[4,4]) parameter(0)
          %i = s32[] get-tuple-element(%p), index=0
          %n = s32[] constant(4)
          ROOT %lt = pred[] compare(%i, %n), direction=LT
        }

        %obody (p: (s32[], f32[4,4])) -> (s32[], f32[4,4]) {
          %p = (s32[], f32[4,4]) parameter(0)
          %x = f32[4,4] get-tuple-element(%p), index=1
          %zero = s32[] constant(0)
          %t0 = (s32[], f32[4,4]) tuple(%zero, %x)
          %w = (s32[], f32[4,4]) while(%t0), condition=%icond, body=%ibody
          %y = f32[4,4] get-tuple-element(%w), index=1
          %i = s32[] get-tuple-element(%p), index=0
          %one = s32[] constant(1)
          %i2 = s32[] add(%i, %one)
          ROOT %t = (s32[], f32[4,4]) tuple(%i2, %y)
        }

        ENTRY %main (a: f32[4,4]) -> f32[4,4] {
          %a = f32[4,4] parameter(0)
          %zero = s32[] constant(0)
          %t0 = (s32[], f32[4,4]) tuple(%zero, %a)
          %w = (s32[], f32[4,4]) while(%t0), condition=%ocond, body=%obody
          ROOT %out = f32[4,4] get-tuple-element(%w), index=1
        }
        """)
        st = H.analyze(hlo)
        # inner dot 2*16*4=128 flops x3 inner x4 outer
        assert st.flops == 128 * 3 * 4


SMOKE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, {src!r})
import jax
from repro.launch import specs, hlo_analysis
from repro.configs import get_config

# miniature production mesh (2x4) standing in for (16x16)
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cell = specs.input_specs("granite-8b", "train_4k", mesh)
with jax.sharding.set_mesh(mesh):
    lowered = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                      out_shardings=cell.out_shardings,
                      donate_argnums=cell.donate).lower(*cell.args)
    compiled = lowered.compile()
mem = compiled.memory_analysis()
assert mem.argument_size_in_bytes > 0
st = hlo_analysis.analyze(compiled.as_text())
assert st.flops > 0 and st.wire_bytes > 0
assert 36 in st.trip_counts.values()   # granite has 36 layers scanned
print("SMOKE_DRYRUN_OK flops=%g wire=%g" % (st.flops, st.wire_bytes))
"""


def test_dryrun_cell_smoke_8_devices():
    """Full lower+compile+analyze path on a small mesh in a subprocess."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = SMOKE_SCRIPT.format(src=os.path.abspath(src))
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SMOKE_DRYRUN_OK" in proc.stdout


def test_input_specs_all_cells_constructible():
    """Every (arch x shape) cell must build its specs (no device state)."""
    import jax

    from repro.launch import specs
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    n = 0
    for arch, shape in specs.all_cells():
        cell = specs.input_specs(arch, shape, mesh)
        assert cell.model_flops > 0
        n += 1
    assert n == 34

    skips = list(specs.skipped_cells())
    assert len(skips) == 6
    assert n + len(skips) == 40   # the full assignment grid


def test_compile_cache_is_left_alone_off_the_chip(monkeypatch):
    import jax

    from repro.launch import cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert cache.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
    assert cache.compile_cache_stats() == {"hits": 0, "misses": 0}
    # the fixed in-checkout path, never a temporary one
    assert cache.CACHE_DIR.name == ".jax_cache"
    assert (cache.CACHE_DIR.parent / "chip_smoke.py").exists()
