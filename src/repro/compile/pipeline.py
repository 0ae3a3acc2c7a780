"""The plan -> executable pipeline:  (TensorAlgebra, Dataflow) -> callable.

This is the missing right half of the paper's Fig. 2 on the TPU retarget
(module selection *and connection*, §V): where the repo previously stopped
at ``KernelPlan.template`` — a string — ``lower`` turns the classification
into a runnable, validated kernel:

    1. ``plan.kernel_plan_for`` picks the Pallas template (paper's module
       selection, a total function of the classification),
    2. the algebra lowering (``lowering.lower_form``) maps the loop nest
       onto the template's batched-matmul interface (im2col /
       mode-unfolding / grid-folded batch dims — the paper's
       template-reuse claim, in code, executing exactly the algebra's
       MACs),
    3. the *shared*, batch-aware tile chooser (``core.tiling`` — the same
       one the cost model prices with) maps the STT tile onto GEMM block
       sizes via ``tiling.form_blocks``, and ``stt_gemm.legal_blocks``
       rounds each up to the least block Mosaic accepts (lane/sublane
       multiples or the full extent),
    4. the result is cached on (algebra, dataflow, shapes, dtype,
       interpret, backend, array config) so serving / benchmark paths
       never re-trace, and
    5. small problems are validated against ``alg.reference`` at lower
       time (larger ones on demand via ``CompiledKernel.validate``).
"""
from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import plan as plan_mod
from ..core import stt as stt_mod
from ..core import tiling
from ..core.algebra import TensorAlgebra
from ..core.costmodel import CostReport, PaperCycleModel
from ..core.stt import Dataflow
from ..core.tiling import ArrayConfig
from ..kernels import epilogue as epilogue_mod
from ..kernels import fused_chain as fused_chain_mod
from ..kernels import ops
from ..kernels import stt_gemm
from .lowering import LoweredForm, lower_form

#: auto-validate at lower time below this many MACs (a pure-python oracle
#: loop; ~1s at the limit, so big sweep/serving shapes skip it)
VALIDATE_MACS_LIMIT = 300_000


@dataclasses.dataclass
class CompiledKernel:
    """A lowered, executable tensor-algebra kernel.

    Call it with a dict of operand arrays (the algebra's input tensor
    names) and it returns the output tensor, computed by the Pallas
    template the dataflow classification selected.
    """

    algebra: TensorAlgebra
    dataflow: Dataflow
    plan: plan_mod.ExecutionPlan
    form: LoweredForm
    blocks: Tuple[int, int, int]        # chip-legal (bm, bn, bk)
    stationary: str                     # GEMM operand pinned in VMEM
    cfg: ArrayConfig
    dtype: jnp.dtype
    interpret: bool
    backend: str
    #: measured-autotuning knobs (kernels/stt_gemm.py): contraction grid
    #: order and accumulation strategy; "default"/"auto" = the analytical
    #: pipeline's historic behavior
    grid_order: str = "default"
    accum: str = "auto"
    #: epilogue ops fused into the kernel's output-block flush
    #: (``kernels/epilogue.py``); () = plain algebra
    epilogue: Tuple[str, ...] = ()
    #: operand-dict key carrying the rank-1 bias vector a "bias" epilogue
    #: op streams (not an algebra tensor; None when the epilogue has none)
    bias_tensor: Optional[str] = None
    #: identity of the fused graph group this kernel was lowered for
    #: (``repro.graph``); part of the compile/tune cache key so a
    #: block-constrained fused lowering never aliases the standalone one
    fused_group: Optional[str] = None
    #: where the blocks/knobs came from: "analytical" (shared tile
    #: chooser) or "tuned" (measured-autotuning cache, repro.tune)
    source: str = "analytical"
    #: median measured wall-clock seconds for this kernel, when the tuner
    #: has timed it (drives CostReport.measured_cycles)
    measured_s: Optional[float] = None
    validated: bool = False
    _report: Optional[CostReport] = dataclasses.field(
        default=None, repr=False)

    @property
    def template(self) -> str:
        return self.plan.kernel.template

    @property
    def gemm(self) -> LoweredForm:
        """Back-compat accessor: the lowered form (historic field name)."""
        return self.form

    @property
    def sparse(self):
        """The structured block-sparse operand (OperandSparsity) or None."""
        return self.form.sparse

    @property
    def sparse_mode(self) -> str:
        """``bsr`` (grid skips zero blocks), ``masked`` (sparse algebra,
        dense execution on zero-masked operands; batched forms skip
        all-zero batch slices — see ``LoweredForm.batch_keep``), or
        ``dense``."""
        if self.form.sparse is not None:
            return "bsr"
        return "masked" if self.algebra.is_sparse else "dense"

    def partition_for(self, shape: Tuple[int, int],
                      axes: Tuple[str, str] = ("x", "y"), *,
                      shard_batch: bool = True,
                      compressed: Optional[bool] = None):
        """Solve this kernel's mesh partition for a mesh shape without
        binding devices (:func:`repro.core.plan.solve_partition` over the
        generated CommPlan + this LoweredForm) — what the cost model, the
        DSE and ``Accelerator.describe()`` consume."""
        return plan_mod.solve_partition(
            self.plan.comm, self.form, axes=axes, shape=shape,
            shard_batch=shard_batch, compressed=compressed)

    def cast_operands(self, operands: Dict[str, jax.Array]
                      ) -> Dict[str, jax.Array]:
        """Cast to the kernel dtype and *enforce* every attached sparsity
        pattern (zero outside the nonzero blocks).  Masking here makes the
        pattern part of the kernel's semantics on every path: the BSR grid
        (which never reads out-of-pattern blocks), the masked-dense
        fallback, and the mesh program all compute the same function of
        the same operands — even when a caller passes unmasked data."""
        cast = {name: jnp.asarray(v).astype(self.dtype)
                for name, v in operands.items()}
        for name, sp in self.algebra.sparsity:
            t = next(t for t in self.algebra.tensors if t.name == name)
            mask = jnp.asarray(sp.element_mask(self.algebra.tensor_shape(t)))
            # select, don't multiply: out-of-pattern inf/nan must drop out
            cast[name] = jnp.where(mask, cast[name],
                                   jnp.zeros((), self.dtype))
        return cast

    def __call__(self, operands: Dict[str, jax.Array]) -> jax.Array:
        bias = None
        if self.bias_tensor is not None:
            if self.bias_tensor not in operands:
                raise ValueError(
                    f"kernel has a fused bias epilogue: operands must "
                    f"include {self.bias_tensor!r}")
            operands = dict(operands)
            bias = jnp.asarray(operands.pop(self.bias_tensor),
                               jnp.float32)
        cast = self.cast_operands(operands)
        lhs, rhs = self.form.prepare(cast)
        bm, bn, bk = self.blocks
        sp = self.form.sparse
        if sp is not None:
            sp_arr, dense_arr = (lhs, rhs) if sp.side == "lhs" else (rhs, lhs)
            out2d = ops.bsr_matmul(
                sp_arr, dense_arr, coords=sp.coords, block=sp.block,
                bstream=bn if sp.side == "lhs" else bm, side=sp.side,
                backend=self.backend, interpret=self.interpret)
            if self.epilogue:
                # the BSR grid has no epilogue flush point yet; apply on
                # the full 2-D output (same math, one extra VMEM pass)
                out2d = epilogue_mod.apply_epilogue(
                    out2d.astype(jnp.float32), self.epilogue,
                    bias=bias).astype(self.dtype)
        else:
            out2d = ops.stt_matmul(
                lhs, rhs, template=self.template, stationary=self.stationary,
                bm=bm, bn=bn, bk=bk, backend=self.backend,
                interpret=self.interpret,
                vmem_budget=self.cfg.vmem_budget_bytes,
                grid_order=self.grid_order, accum=self.accum,
                epilogue=self.epilogue, bias=bias)
        return self.form.finish(out2d)

    def validate(self, seed: int = 0, atol: float = 1e-3) -> float:
        """Execute on random operands and compare against the loop-nest
        oracle ``alg.reference`` (composed with the numpy epilogue mirror
        when ops are fused).  Returns the max abs error; raises on
        mismatch.  Integer-valued operands make the fp32 path exact for
        every registry shape that fits the oracle."""
        operands = dict(self.algebra.random_operands(seed))
        bias = None
        if self.bias_tensor is not None:
            n_last = self.algebra.tensor_shape(self.algebra.output)[-1]
            bias = np.random.default_rng(seed + 1).integers(
                -4, 5, size=(n_last,)).astype(np.float64)
            operands[self.bias_tensor] = bias
        got = np.asarray(self(operands), dtype=np.float64)
        want = self.algebra.reference(
            {k: v for k, v in operands.items()
             if k != self.bias_tensor}).astype(np.float64)
        if self.epilogue:
            want = epilogue_mod.apply_epilogue_np(want, self.epilogue,
                                                  bias=bias)
        err = float(np.abs(got - want).max()) if got.size else 0.0
        if got.shape != want.shape or err > atol:
            raise AssertionError(
                f"lowered {self.algebra.name} x {self.dataflow.name} "
                f"diverged from reference: shape {got.shape} vs "
                f"{want.shape}, max err {err:.3e}")
        self.validated = True
        return err

    def cost_report(self) -> CostReport:
        """The cost model's view of this exact (algebra, dataflow, config)
        — same tile chooser, so priced and executed tiles agree.  When the
        measured autotuner has timed this kernel (``measured_s``), the
        report carries the measurement as ``measured_cycles`` at the
        model's clock, so modeled and measured sit side by side."""
        if self._report is None:
            self._report = PaperCycleModel(self.cfg).evaluate(
                self.algebra, self.dataflow)
        if self.measured_s is not None:
            mc = self.measured_s * self.cfg.freq_mhz * 1e6
            if self._report.measured_cycles != mc:
                # re-attach on every change: the compile cache shares this
                # object, and a re-tune may update measured_s in place
                self._report = dataclasses.replace(
                    self._report, measured_cycles=mc)
        return self._report


# ---------------------------------------------------------------------------
# Compile cache — bounded LRU, safe under concurrent lowers (serving
# processes lower from request threads; an unbounded dict would grow with
# every distinct shape and race on simultaneous inserts).
# ---------------------------------------------------------------------------

#: default cap; generous for benchmarks (the full registry x named-STT
#: matrix is 24 entries) while bounding long-running serving processes.
DEFAULT_CACHE_CAPACITY = 256

_CACHE: "collections.OrderedDict[Tuple, CompiledKernel]" = (
    collections.OrderedDict())
_CACHE_LOCK = threading.Lock()
_CAPACITY = DEFAULT_CACHE_CAPACITY
_STATS = {"hits": 0, "misses": 0, "evictions": 0}


def _cache_key(alg: TensorAlgebra, df: Dataflow, cfg: ArrayConfig,
               dtype, interpret: bool, backend: str,
               epilogue: Tuple[str, ...] = (),
               bias_tensor: Optional[str] = None,
               fused_group: Optional[str] = None) -> Tuple:
    # alg is a frozen dataclass of tuples: it *is* the algebra signature
    # (name + loops + bounds/shapes + access matrices + sparsity), and the
    # LoweredForm — batch grid dims included — is a pure function of it,
    # so the key needs no separate form component.  The dataflow key adds
    # the selection, the exact T and the per-tensor classification.
    #
    # This tuple is also the identity the on-disk *tuning* cache hashes
    # (repro.tune.cache.key_for): a tuned variant applies exactly where
    # the compiled kernel it was measured on would be reused.  The
    # epilogue spec and the fused-group id are part of that identity: an
    # epilogue'd kernel computes a different function, and a fused-graph
    # lowering constrains the block schedule — a variant tuned for the
    # standalone algebra must not be replayed for either.
    return (alg, df.selected, df.T, df.signature, cfg,
            jnp.dtype(dtype).name, interpret, backend,
            tuple(epilogue), bias_tensor, fused_group)


def _variant_key(key: Tuple, blocks, grid_order: str, accum: str) -> Tuple:
    """Extend the base key with the knob values a kernel was built with
    (``blocks=None`` = the analytical tile chooser's blocks, which are a
    pure function of the base key)."""
    return key + (blocks, grid_order, accum)


def cache_info() -> Dict[str, int]:
    with _CACHE_LOCK:
        return {"size": len(_CACHE), "capacity": _CAPACITY, **_STATS}


def cache_clear() -> None:
    with _CACHE_LOCK:
        _CACHE.clear()
        _STATS["hits"] = _STATS["misses"] = _STATS["evictions"] = 0


def cache_resize(capacity: int) -> None:
    """Set the LRU capacity, evicting least-recently-used entries now if
    the cache is over the new cap."""
    if capacity < 1:
        raise ValueError("cache capacity must be >= 1")
    global _CAPACITY
    with _CACHE_LOCK:
        _CAPACITY = capacity
        while len(_CACHE) > _CAPACITY:
            _CACHE.popitem(last=False)
            _STATS["evictions"] += 1


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def default_dataflow(alg: TensorAlgebra) -> Dataflow:
    """A sane default schedule: output-stationary STT over the first three
    loop iterators (every Table II algebra admits it)."""
    return stt_mod.apply_stt(alg, alg.loops[:3],
                             stt_mod.stt_from_name("output_stationary"))


def _blocks_from_tile(alg: TensorAlgebra, df: Dataflow, form: LoweredForm,
                      cfg: ArrayConfig) -> Tuple[int, int, int]:
    """Map the STT tile (per selected loop) onto GEMM block sizes via the
    shared, batch-aware chooser (``core.tiling.form_blocks``): loops
    folded onto the batch grid dims never inflate a block.  ``lower``
    then rounds them up to blocks the chip's compiler accepts
    (``stt_gemm.legal_blocks``); the cost model keeps pricing the tile."""
    return tiling.form_blocks(alg, df, form, cfg.pe_dims)


def _epilogue_legal_for_form(alg: TensorAlgebra, form: LoweredForm,
                             epilogue: Tuple[str, ...]) -> Optional[str]:
    """Why this epilogue cannot ride this lowered form (None = legal).

    Elementwise ops commute with the finish reshape, so they are legal on
    every form.  ``bias`` / ``softmax`` act along the last axis: they are
    only legal when the finished tensor's last axis *is* the matmul n
    axis (gemm's identity finish is the canonical case) — otherwise the
    2-D in-kernel application and the finished-tensor semantics diverge.
    """
    rowwise = (epilogue_mod.needs_bias(epilogue)
        or epilogue_mod.has_softmax(epilogue))
    if not rowwise:
        return None
    out_shape = alg.tensor_shape(alg.output)
    if form.batch or out_shape[-1] != form.n:
        return (f"bias/softmax epilogue acts on the matmul n axis "
                f"(n={form.n}) but the finished output {out_shape} of "
                f"{alg.name} does not end with it")
    return None


def lower(alg: TensorAlgebra, df: Optional[Dataflow] = None, *,
          cfg: ArrayConfig = ArrayConfig(),
          dtype=jnp.float32, interpret: bool = False,
          backend: str = "pallas",
          validate: Optional[bool] = None,
          blocks: Optional[Tuple[int, int, int]] = None,
          grid_order: Optional[str] = None,
          accum: Optional[str] = None,
          tuned: Optional[bool] = None,
          epilogue: Sequence[str] = (),
          bias_tensor: Optional[str] = None,
          fused_group: Optional[str] = None) -> CompiledKernel:
    """Lower ``(algebra, dataflow)`` to an executable, cached kernel.

    ``validate=None`` (default) auto-validates against ``alg.reference``
    when the problem is small enough for the python oracle; pass True to
    force (may be slow) or False to skip.

    ``blocks`` / ``grid_order`` / ``accum`` override the analytical tile
    chooser and the kernel-knob defaults (the measured autotuner's search
    axes).  When none are given and ``tuned`` is not False, the on-disk
    tuning cache (``repro.tune``) is consulted first — a persisted winner
    for this exact compile key replaces the analytical choice, which is
    how a ``repro.tune.tune()`` run keeps paying off in later processes.

    ``epilogue`` fuses post-processing ops (``kernels/epilogue.py``) into
    the kernel's output-block flush; a ``"bias"`` op names its extra
    rank-1 operand via ``bias_tensor`` (the ``__call__`` dict key).
    ``fused_group`` tags a lowering constrained by a fused graph
    (``repro.graph``); all three enter the compile *and* tuning cache
    keys, so standalone and fused variants never alias.
    """
    if df is None:
        df = default_dataflow(alg)
    if df.algebra_name != alg.name:
        raise ValueError(f"dataflow {df.name} was generated for algebra "
                         f"{df.algebra_name!r}, not {alg.name!r}")
    epilogue = epilogue_mod.validate_spec(epilogue)
    if epilogue_mod.needs_bias(epilogue) and bias_tensor is None:
        raise ValueError("epilogue with a 'bias' op needs bias_tensor= "
                         "(the operand-dict key of the bias vector)")
    if bias_tensor is not None and not epilogue_mod.needs_bias(epilogue):
        raise ValueError("bias_tensor= given but the epilogue has no "
                         "'bias' op")
    if bias_tensor is not None and any(t.name == bias_tensor
                                       for t in alg.tensors):
        raise ValueError(f"bias_tensor {bias_tensor!r} collides with an "
                         f"algebra tensor name")
    key = _cache_key(alg, df, cfg, dtype, interpret, backend,
                     epilogue, bias_tensor, fused_group)
    source, measured_s = "analytical", None
    if (blocks is None and grid_order is None and accum is None
            and tuned is not False):
        # consult the measured-tuning cache before the analytical chooser
        from ..tune import cache as tune_cache
        entry = tune_cache.lookup_variant(tune_cache.key_of(key))
        if entry is not None:
            blocks = tuple(entry["blocks"])
            grid_order = entry["grid_order"]
            accum = entry["accum"]
            source = "tuned"
            measured_s = entry.get("measured_s")
    grid_order = "default" if grid_order is None else grid_order
    accum = "auto" if accum is None else accum
    key = _variant_key(key, blocks, grid_order, accum)
    with _CACHE_LOCK:
        hit = _CACHE.get(key)
        if hit is not None:
            _STATS["hits"] += 1
            _CACHE.move_to_end(key)
        else:
            _STATS["misses"] += 1
    if hit is not None:
        if not hit.validated and (
                validate or (validate is None
                             and alg.total_macs() <= VALIDATE_MACS_LIMIT)):
            # an earlier lower(validate=False) cached it unvalidated;
            # honour the explicit or auto-validate request now (outside
            # the lock — the python oracle can be slow)
            hit.validate()
        return hit

    ep = plan_mod.plan_for(
        df, densities={name: alg.density_of(name) for name, _ in alg.sparsity})
    form = lower_form(alg)
    if epilogue:
        reason = _epilogue_legal_for_form(alg, form, epilogue)
        if reason is not None:
            raise ValueError(reason)
    stationary = ("A" if ep.kernel.resident_tensor in form.lhs_tensors
        else "B")
    if blocks is None:
        blocks = _blocks_from_tile(alg, df, form, cfg)
    softmax = epilogue_mod.has_softmax(epilogue)
    if softmax:
        # a row softmax needs the whole unpadded row in one block
        blocks = (blocks[0], form.n, blocks[2])
    template = ep.kernel.template
    if epilogue and template == "operand_stationary" and stationary == "A":
        template = "output_stationary"      # ops.stt_matmul reroutes it
    if form.sparse is None:
        # blocks Mosaic accepts, within the scoped VMEM limit
        blocks = stt_gemm.fit_blocks(
            template, (form.m, form.n, form.k), blocks, dtype,
            cfg.vmem_budget_bytes, stationary=stationary, keep_n=softmax)
    kernel = CompiledKernel(
        algebra=alg, dataflow=df, plan=ep, form=form, blocks=blocks,
        stationary=stationary, cfg=cfg, dtype=jnp.dtype(dtype),
        interpret=interpret, backend=backend,
        epilogue=epilogue, bias_tensor=bias_tensor,
        fused_group=fused_group,
        grid_order=grid_order, accum=accum, source=source,
        measured_s=measured_s)
    if validate or (validate is None
                    and alg.total_macs() <= VALIDATE_MACS_LIMIT):
        kernel.validate()
    with _CACHE_LOCK:
        prior = _CACHE.get(key)
        if prior is not None:
            # a concurrent lower built the same kernel first; keep the
            # cached one so callers always share a single object per key
            _CACHE.move_to_end(key)
            return prior
        _CACHE[key] = kernel
        while len(_CACHE) > _CAPACITY:
            _CACHE.popitem(last=False)
            _STATS["evictions"] += 1
    return kernel


# ---------------------------------------------------------------------------
# Merged fused-group lowering — one CompiledGroupKernel per chain
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CompiledGroupKernel:
    """An entire fused graph group lowered to ONE Pallas kernel.

    Two templates share this wrapper.  ``kind == "chain"`` (the streamed
    lhs ladder): ``__call__(lhs, rhss, biases)`` takes the group's
    external operands in *storage* layout (gemm weights are ``(n, k)``;
    the transpose the per-node ``prepare`` would apply happens here) and
    returns the group's result edge.  ``kind == "dag"`` (stage-major:
    rhs-landing edges, batched stages, residuals, taps):
    ``__call__(exts)`` takes ONE sequence of external operands matching
    ``ext_roles`` order — again in storage layout, role casts applied
    here — and returns ``(result, *taps)``.  Either way every
    non-tapped intermediate stays in VMEM scratch inside the single
    ``pallas_call`` (``kernels/fused_chain.py``).
    """

    group: str                          # FusedGroupPlan.name
    stages: Tuple[str, ...]             # member node names (labels)
    chain: Tuple[fused_chain_mod.ChainStage, ...]
    m: int
    k0: int
    bm: int                             # m-block (grid phases)
    interleave: str                     # "chain" | "stage" | "dag"
    cfg: ArrayConfig
    dtype: jnp.dtype
    interpret: bool
    backend: str
    kind: str = "chain"                 # "chain" | "dag"
    dag: Tuple[fused_chain_mod.DagStage, ...] = ()
    ext_roles: Tuple[Tuple[str, str], ...] = ()     # (edge, role)
    ext_shapes: Tuple[Tuple[int, ...], ...] = ()    # storage shapes
    n_tap: int = 0
    #: where bm/interleave came from: "analytical" (the plan's agreed
    #: blocks) or "tuned" (the on-disk group tuning cache)
    source: str = "analytical"
    #: merged / sequential medians when the group tuner measured them
    measured_s: Optional[float] = None
    sequential_s: Optional[float] = None
    validated: bool = False
    #: the jitted end-to-end entry (casts + transposes + megakernel in
    #: ONE dispatch — per-call eager ops would cost more than the merge
    #: saves); built lazily on first call
    _fn: Optional[object] = dataclasses.field(
        default=None, repr=False, compare=False)

    def total_macs(self) -> int:
        if self.kind == "dag":
            return sum(st.m * st.k * st.n for st in self.dag)
        return sum(self.m * st.k * st.n for st in self.chain)

    @staticmethod
    def _dag_prep(ext, role, dtype):
        """Storage layout -> kernel-facing layout, per operand role."""
        if role == "rhs":
            return ext.astype(dtype).T          # (n, k) storage -> (k, n)
        if role == "res":
            return ext.astype(jnp.float32)
        if role == "bias":
            return ext.astype(jnp.float32).reshape(1, -1)
        return ext.astype(dtype)                # lhs / a3d / vec

    def _build_fn(self):
        dtype, interpret = self.dtype, self.interpret
        xla = self.backend == "xla"
        if self.kind == "dag":
            dag, roles = self.dag, tuple(r for _, r in self.ext_roles)

            @jax.jit
            def fn(exts):
                prepped = tuple(self._dag_prep(e, role, dtype)
                                for e, role in zip(exts, roles))
                if xla:
                    return fused_chain_mod.dag_reference(
                        prepped, stages=dag, out_dtype=dtype)
                return fused_chain_mod.fused_dag(
                    prepped, stages=dag, out_dtype=dtype,
                    interpret=interpret)

            return fn
        stages, out_name = self.chain, dtype.name
        bm, interleave = self.bm, self.interleave

        @jax.jit
        def fn(lhs, rhss, biases):
            lhs = lhs.astype(dtype)
            # gemm stores B as (n, k); the merged template wants (k, n)
            rhs_kn = tuple(r.astype(dtype).T for r in rhss)
            rows = tuple(b.astype(jnp.float32).reshape(-1) for b in biases)
            if xla:
                return fused_chain_mod.chain_reference(
                    lhs, *rhs_kn, *(r.reshape(1, -1) for r in rows),
                    stages=stages, out_dtype=out_name)
            return fused_chain_mod.fused_chain_matmul(
                lhs, rhs_kn, rows, stages=stages, bm=bm,
                interleave=interleave, out_dtype=dtype,
                interpret=interpret)

        return fn

    def __call__(self, lhs, rhss: Sequence[jax.Array] = (),
                 biases: Sequence[jax.Array] = ()):
        if self._fn is None:
            self._fn = self._build_fn()
        if self.kind == "dag":
            # single argument: the ext_roles-ordered operand sequence
            return self._fn(tuple(jnp.asarray(e) for e in lhs))
        return self._fn(jnp.asarray(lhs),
                        tuple(jnp.asarray(r) for r in rhss),
                        tuple(jnp.asarray(b) for b in biases))

    def validate(self, seed: int = 0, atol: float = 1e-3,
                 rtol: Optional[float] = None) -> float:
        """Run on random integer operands and compare against the fp64
        numpy chain oracle (dot + ``apply_epilogue_np`` per stage).
        ``rtol`` scales with the output magnitude (a chain compounds
        rounding); defaults per dtype."""
        if rtol is None:
            rtol = 1e-5 if self.dtype == jnp.float32 else 2e-2
        rng = np.random.default_rng(seed)
        if self.kind == "dag":
            return self._validate_dag(rng, atol, rtol)
        lhs = rng.integers(-4, 5, size=(self.m, self.k0))
        rhss = [rng.integers(-4, 5, size=(st.n, st.k))
                for st in self.chain]
        biases = [rng.integers(-4, 5, size=(st.n,))
                  for st in self.chain if st.has_bias]
        got = np.asarray(self(lhs, rhss, biases), dtype=np.float64)
        x = lhs.astype(np.float64)
        bi = 0
        for st, r in zip(self.chain, rhss):
            x = x @ r.T.astype(np.float64)
            if st.epilogue:
                b = None
                if st.has_bias:
                    b = biases[bi].astype(np.float64)
                    bi += 1
                x = epilogue_mod.apply_epilogue_np(x, st.epilogue, bias=b)
        want = x
        err = float(np.abs(got - want).max()) if got.size else 0.0
        bound = atol + rtol * (float(np.abs(want).max()) if want.size
                               else 0.0)
        if got.shape != want.shape or err > bound:
            raise AssertionError(
                f"merged group {self.group} diverged from the chain "
                f"oracle: shape {got.shape} vs {want.shape}, max err "
                f"{err:.3e} (bound {bound:.3e})")
        self.validated = True
        return err

    def _validate_dag(self, rng, atol: float, rtol: float) -> float:
        """DAG branch of :meth:`validate`: random integer operands in
        storage layout, compared (result + every tap) against a fp64
        numpy mirror of the stage list."""
        exts = [rng.integers(-4, 5, size=shape)
                for shape in self.ext_shapes]
        got = tuple(np.asarray(o, dtype=np.float64) for o in self(exts))
        prepped = []
        for e, (_, role) in zip(exts, self.ext_roles):
            a = e.astype(np.float64)
            prepped.append(a.T if role == "rhs" else a)
        vals: list = []
        taps: dict = {}
        for st in self.dag:
            def fetch(src, transpose=False):
                where, idx = src
                buf = prepped[idx] if where == "ext" else vals[idx]
                return buf.T if transpose else buf
            if st.kind == "batched":
                acc = np.einsum("bkn,bk->bn", fetch(st.lhs),
                                fetch(st.rhs))
            else:
                acc = fetch(st.lhs) @ fetch(
                    st.rhs, transpose=st.rhs[0] == "scr")
            if st.epilogue:
                b = (prepped[st.bias].reshape(-1) if st.has_bias
                     else None)
                acc = epilogue_mod.apply_epilogue_np(acc, st.epilogue,
                                                     bias=b)
            y = acc
            if st.res is not None:
                y = y + fetch(st.res)
            vals.append(y)
            if st.tap >= 0:
                taps[st.tap] = y
        wants = (vals[-1],) + tuple(taps[i] for i in sorted(taps))
        err_max = 0.0
        for which, (g, want) in enumerate(zip(got, wants)):
            err = float(np.abs(g - want).max()) if g.size else 0.0
            bound = atol + rtol * (float(np.abs(want).max())
                                   if want.size else 0.0)
            if g.shape != want.shape or err > bound:
                what = "result" if which == 0 else f"tap {which - 1}"
                raise AssertionError(
                    f"merged group {self.group} {what} diverged from "
                    f"the DAG oracle: shape {g.shape} vs {want.shape}, "
                    f"max err {err:.3e} (bound {bound:.3e})")
            err_max = max(err_max, err)
        self.validated = True
        return err_max


def _group_cache_key(plan, group, interpret: bool, backend: str) -> Tuple:
    """The merged-kernel compile/tune-cache identity: ``_cache_key``'s
    per-node components *extended with the stage list* — each stage
    contributes its algebra, dataflow identity, epilogue spec and bias
    presence, in chain order — plus the shared config/dtype/backend.
    Two graphs whose fused chains are structurally identical share the
    entry regardless of node or edge naming.  A ``kind="dag"`` group
    keys on its bound stage list + operand-role order instead — the
    dag template ignores per-node dataflows (everything is whole-tensor
    stage-major), and the hashable :class:`DagStage` tuple already
    encodes shapes, wiring, epilogues and taps."""
    if getattr(group, "kind", "chain") == "dag":
        return ("fused_dag", group.dag,
                tuple(role for _, role in group.ext_inputs),
                plan.cfg, str(plan.dtype), bool(interpret), str(backend))
    stage_ids = []
    for name in group.stages:
        p = plan.nodes[name]
        stage_ids.append((p.node.algebra, p.dataflow.selected,
                          p.dataflow.T, p.dataflow.signature,
                          p.epilogue, p.bias_edge is not None))
    return ("fused_chain", tuple(stage_ids), plan.cfg, str(plan.dtype),
            bool(interpret), str(backend))


def _group_variant_key(key: Tuple, bm: int, interleave: str) -> Tuple:
    return key + (int(bm), str(interleave))


def lower_group(plan, group, *, interpret: bool = False,
                backend: str = "pallas",
                validate: Optional[bool] = None,
                bm: Optional[int] = None,
                interleave: Optional[str] = None,
                tuned: Optional[bool] = None
                ) -> Optional[CompiledGroupKernel]:
    """Lower a :class:`~repro.graph.planner.FusedGroupPlan` to a single
    cached :class:`CompiledGroupKernel` (one ``pallas_call`` for the
    whole chain).

    ``bm`` / ``interleave`` override the plan's agreed m-block and the
    default stage order (the merged-kernel tuner's knobs).  When neither
    is given and ``tuned`` is not False, the on-disk group tuning cache
    is consulted first: a persisted winner supplies the knobs — and a
    persisted *sequential* verdict makes this return ``None``, telling
    the executor to keep per-node dispatch (the tuner measured merged
    slower on this machine).
    """
    if not group.eligible:
        raise ValueError(f"group {group.name} is not merged-eligible: "
                         f"{group.reason}")
    key = _group_cache_key(plan, group, interpret, backend)
    source, measured_s, sequential_s = "analytical", None, None
    if bm is None and interleave is None and tuned is not False:
        from ..tune import cache as tune_cache
        entry = tune_cache.lookup_group(tune_cache.key_of(key))
        if entry is not None:
            if not entry["merged"]:
                return None             # measured verdict: keep sequential
            bm = int(entry["bm"])
            interleave = entry["interleave"]
            source = "tuned"
            measured_s = entry.get("merged_s")
            sequential_s = entry.get("sequential_s")
    is_dag = getattr(group, "kind", "chain") == "dag"
    bm = group.bm if bm is None else bm
    if interleave is None:
        interleave = (fused_chain_mod.DAG_INTERLEAVE if is_dag
                      else "chain")
    allowed = ((fused_chain_mod.DAG_INTERLEAVE,) if is_dag
               else fused_chain_mod.FUSED_INTERLEAVES)
    if interleave not in allowed:
        raise ValueError(f"interleave must be one of {allowed}, "
                         f"got {interleave!r}")
    key = _group_variant_key(key, bm, interleave)
    with _CACHE_LOCK:
        hit = _CACHE.get(key)
        if hit is not None:
            _STATS["hits"] += 1
            _CACHE.move_to_end(key)
        else:
            _STATS["misses"] += 1
    if hit is not None:
        if not hit.validated and (
                validate or (validate is None
                             and hit.total_macs() <= VALIDATE_MACS_LIMIT)):
            hit.validate()
        return hit
    ext_shapes = (tuple(plan.graph.edge_shape(e)
                        for e, _ in group.ext_inputs) if is_dag else ())
    kernel = CompiledGroupKernel(
        group=group.name, stages=tuple(group.stages), chain=group.chain,
        m=group.m, k0=group.k0, bm=bm, interleave=interleave,
        cfg=plan.cfg, dtype=jnp.dtype(plan.dtype), interpret=interpret,
        backend=backend, source=source, measured_s=measured_s,
        sequential_s=sequential_s,
        kind="dag" if is_dag else "chain",
        dag=group.dag if is_dag else (),
        ext_roles=tuple(group.ext_inputs) if is_dag else (),
        ext_shapes=ext_shapes,
        n_tap=len(group.taps) if is_dag else 0)
    if validate or (validate is None
                    and kernel.total_macs() <= VALIDATE_MACS_LIMIT):
        kernel.validate()
    with _CACHE_LOCK:
        prior = _CACHE.get(key)
        if prior is not None:
            _CACHE.move_to_end(key)
            return prior
        _CACHE[key] = kernel
        while len(_CACHE) > _CAPACITY:
            _CACHE.popitem(last=False)
            _STATS["evictions"] += 1
    return kernel
