"""The fused-group megakernel: a whole gemm chain in ONE pallas_call.

PR 8's graph layer *schedules* fusion (tile agreement, folded epilogues)
but still dispatches one Pallas kernel per node, leaving VMEM residency
of the intermediates to XLA.  This template executes an entire fused
group — ``gemm -> gelu -> gemm``, the ``scores -> softmax -> attend``
attention pair, or the full 4-gemm attention+MLP chain — as a single
``pl.pallas_call``: every intermediate lives in a VMEM scratch buffer
and is **never written to HBM**.  This is TensorLib's parameterized-
template idea applied to the multi-op generation unit (TileLoom / LEGO
argue the fused group is the right unit — PAPERS.md).

Shape contract (what the planner's agreement pass guarantees):

* every stage is a 2-D gemm chained on its lhs: stage ``j`` computes
  ``x_{j+1} = cast(epilogue_j(x_j @ rhs_j), dtype)`` with ``x_0`` the
  group's external lhs ``(m, k_0)`` and ``rhs_j`` of shape
  ``(k_j, n_j)`` where ``k_{j+1} == n_j``,
* each ``rhs_j`` (and its optional ``(1, n_j)`` bias row) is fully
  VMEM-resident with its block index pinned — weights are small
  relative to the activation stream,
* only ``m`` is tiled (block ``bm``); each stage's full ``n_j`` row
  is produced at once, so a row ``softmax`` epilogue is always legal
  and the per-stage math is a single ``jnp.dot`` + the same
  ``_flush_block`` the per-node templates use.  With ``bm == m`` (the
  planner's whole-tensor fast path) the merged kernel runs the exact
  instruction sequence of the sequential whole-tensor dispatches —
  bit-identical output, one kernel launch.

Two interleave orders (the tuner's stage-order knob):

* ``"chain"`` — grid ``(m/bm,)``: all stages run back-to-back per
  m-block; intermediate scratch is one ``(bm, n_j)`` strip per stage.
* ``"stage"`` — grid ``(S, m/bm)`` stage-major: phase ``s`` runs stage
  ``s`` over every m-block (``pl.when(program_id(0) == j)``) before the
  next stage starts; scratch holds the full ``(m, n_j)`` intermediate.
  Trades scratch footprint for weight-stationarity: each ``rhs_j`` is
  touched in exactly one contiguous phase.

``m`` not divisible by ``bm`` is handled by zero-padding the lhs rows
and slicing the output; epilogues (bias/softmax) make padded rows
nonzero but never leak across rows, so the slice is exact.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import epilogue as _ep
from .stt_gemm import _flush_block, mxu_dot

#: valid stage interleave orders (the merged-kernel tuner knob)
FUSED_INTERLEAVES = ("chain", "stage")


@dataclasses.dataclass(frozen=True)
class ChainStage:
    """One gemm stage of a fused chain (hashable: jit-static + cache
    key component).  ``k`` is the stage's contraction extent (== the
    previous stage's ``n``), ``epilogue`` the in-kernel spec applied to
    the fp32 product, ``has_bias`` whether the spec streams a bias row.
    """

    k: int
    n: int
    epilogue: Tuple[str, ...] = ()
    has_bias: bool = False


def validate_chain(stages: Sequence[ChainStage], k0: int
                   ) -> Tuple[ChainStage, ...]:
    """Normalize + validate a stage list: shapes chain, epilogues parse,
    bias flags agree with the specs."""
    stages = tuple(stages)
    if not stages:
        raise ValueError("a fused chain needs at least one stage")
    k = k0
    for j, st in enumerate(stages):
        if st.k != k:
            raise ValueError(
                f"stage {j} contracts over k={st.k} but receives a "
                f"(m, {k}) input; stages must chain n -> k")
        if st.k <= 0 or st.n <= 0:
            raise ValueError(f"stage {j} has non-positive dims "
                             f"({st.k}, {st.n})")
        spec = _ep.validate_spec(st.epilogue)
        if _ep.needs_bias(spec) != st.has_bias:
            raise ValueError(
                f"stage {j} epilogue {spec} "
                f"{'needs' if _ep.needs_bias(spec) else 'has no'} bias "
                f"but has_bias={st.has_bias}")
        k = st.n
    return stages


# ---------------------------------------------------------------------------
# VMEM footprint estimates — what the planner's budget gate prices
# ---------------------------------------------------------------------------

def chain_scratch_bytes(stages: Sequence[ChainStage], bm: int,
                        itemsize: int) -> int:
    """Intermediate scratch for ``interleave='chain'``: one ``(bm, n)``
    strip per non-final stage, in the chain dtype."""
    return sum(bm * st.n * itemsize for st in tuple(stages)[:-1])


def stage_scratch_bytes(stages: Sequence[ChainStage], m: int,
                        itemsize: int) -> int:
    """Intermediate scratch for ``interleave='stage'``: the full
    ``(m, n)`` tensor per non-final stage survives across phases."""
    return sum(m * st.n * itemsize for st in tuple(stages)[:-1])


def chain_vmem_bytes(stages: Sequence[ChainStage], m: int, k0: int,
                     bm: int, itemsize: int,
                     interleave: str = "chain") -> int:
    """Total VMEM residency estimate of the merged kernel: the Pallas
    pipeline double-buffers every input and output block (lhs block, all
    pinned rhs, fp32 bias rows, output block), plus the fp32 result of
    the widest stage's dot and the intermediate scratch; fp32 operands
    add their bf16 split parts (see ``stt_gemm.vmem_bytes``).  The
    planner compares this against the array config's
    ``vmem_budget_bytes`` (the chip's scoped VMEM limit) before
    committing to a merged lowering."""
    stages = tuple(stages)
    operands = bm * k0 * itemsize                     # lhs block
    operands += sum(st.k * st.n * itemsize for st in stages)   # weights
    resident = operands
    resident += sum(4 * st.n for st in stages if st.has_bias)  # bias rows
    resident += bm * stages[-1].n * itemsize          # output block
    resident *= 2                                     # double-buffered
    resident += max(bm * st.n * 4 for st in stages)   # fp32 dot result
    if itemsize == 4:
        resident += 2 * operands                      # bf16 split parts
    if interleave == "stage":
        resident += stage_scratch_bytes(stages, m, itemsize)
    else:
        resident += chain_scratch_bytes(stages, bm, itemsize)
    return resident


# ---------------------------------------------------------------------------
# Kernel bodies
# ---------------------------------------------------------------------------

def _split_refs(refs, n_stage: int, n_bias: int):
    """Unpack the flat pallas ref list: lhs, rhs*, bias*, out, scratch*."""
    lhs_ref = refs[0]
    rhs_refs = refs[1:1 + n_stage]
    bias_refs = refs[1 + n_stage:1 + n_stage + n_bias]
    o_ref = refs[1 + n_stage + n_bias]
    scr_refs = refs[2 + n_stage + n_bias:]
    return lhs_ref, rhs_refs, bias_refs, o_ref, scr_refs


def _stage_bias_refs(stages, bias_refs):
    """Per-stage bias ref (None for stages without one)."""
    out, bi = [], 0
    for st in stages:
        if st.has_bias:
            out.append(bias_refs[bi])
            bi += 1
        else:
            out.append(None)
    return out


def _chain_kernel(*refs, stages: Tuple[ChainStage, ...], n_bias: int,
                  mid_dtype, out_dtype):
    """interleave='chain': all stages back-to-back for one m-block."""
    lhs_ref, rhs_refs, bias_refs, o_ref, scr = _split_refs(
        refs, len(stages), n_bias)
    biases = _stage_bias_refs(stages, bias_refs)
    x = lhs_ref[...]
    for j, st in enumerate(stages):
        acc = mxu_dot(x, rhs_refs[j][...])
        if j + 1 < len(stages):
            scr[j][...] = _flush_block(acc, biases[j], st.epilogue,
                                       mid_dtype)
            x = scr[j][...]
        else:
            o_ref[...] = _flush_block(acc, biases[j], st.epilogue,
                                      out_dtype)


def _stage_kernel(*refs, stages: Tuple[ChainStage, ...], n_bias: int,
                  bm: int, mid_dtype, out_dtype):
    """interleave='stage': grid (S, m/bm); phase s runs stage s over
    every m-block before phase s+1 starts (enforced by the 'arbitrary'
    grid semantics), reading/writing full-tensor scratch rows."""
    lhs_ref, rhs_refs, bias_refs, o_ref, scr = _split_refs(
        refs, len(stages), n_bias)
    biases = _stage_bias_refs(stages, bias_refs)
    s = pl.program_id(0)
    row = pl.ds(pl.program_id(1) * bm, bm)
    for j, st in enumerate(stages):
        @pl.when(s == j)
        def _run(j=j, st=st):
            x = lhs_ref[...] if j == 0 else scr[j - 1][row, :]
            acc = mxu_dot(x, rhs_refs[j][...])
            if j + 1 < len(stages):
                scr[j][row, :] = _flush_block(acc, biases[j], st.epilogue,
                                              mid_dtype)
            else:
                o_ref[...] = _flush_block(acc, biases[j], st.epilogue,
                                          out_dtype)


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("stages", "bm", "interleave", "out_dtype",
                     "interpret"))
def _fused_chain(lhs, *operands, stages: Tuple[ChainStage, ...],
                 bm: int, interleave: str, out_dtype: str,
                 interpret: bool):
    n_stage = len(stages)
    n_bias = sum(1 for st in stages if st.has_bias)
    rhss = operands[:n_stage]
    bias_rows = operands[n_stage:]
    m = lhs.shape[0]
    mid_dtype = lhs.dtype
    n_last = stages[-1].n

    mp = -(-m // bm) * bm
    if mp != m:
        lhs = jnp.pad(lhs, ((0, mp - m), (0, 0)))
    n_m = mp // bm

    if interleave == "chain":
        grid = (n_m,)
        imap_m = lambda i: (i, 0)           # noqa: E731
        imap_pin = lambda i: (0, 0)         # noqa: E731
        kernel = functools.partial(
            _chain_kernel, stages=stages, n_bias=n_bias,
            mid_dtype=mid_dtype, out_dtype=jnp.dtype(out_dtype))
        scratch = [pltpu.VMEM((bm, st.n), mid_dtype)
                   for st in stages[:-1]]
        semantics = ("parallel",)
    else:
        grid = (n_stage, n_m)
        imap_m = lambda s, i: (i, 0)        # noqa: E731
        imap_pin = lambda s, i: (0, 0)      # noqa: E731
        kernel = functools.partial(
            _stage_kernel, stages=stages, n_bias=n_bias, bm=bm,
            mid_dtype=mid_dtype, out_dtype=jnp.dtype(out_dtype))
        scratch = [pltpu.VMEM((mp, st.n), mid_dtype)
                   for st in stages[:-1]]
        semantics = ("arbitrary", "arbitrary")

    in_specs = [pl.BlockSpec((bm, stages[0].k), imap_m)]
    in_specs += [pl.BlockSpec((st.k, st.n), imap_pin) for st in stages]
    in_specs += [pl.BlockSpec((1, st.n), imap_pin)
                 for st in stages if st.has_bias]

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, n_last), imap_m),
        out_shape=jax.ShapeDtypeStruct((mp, n_last), jnp.dtype(out_dtype)),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics),
        interpret=interpret,
    )(lhs, *rhss, *bias_rows)
    return out[:m] if mp != m else out


def fused_chain_matmul(lhs: jax.Array,
                       rhss: Sequence[jax.Array],
                       biases: Sequence[jax.Array] = (), *,
                       stages: Sequence[ChainStage],
                       bm: Optional[int] = None,
                       interleave: str = "chain",
                       out_dtype=None,
                       interpret: bool = False,
                       vmem_budget: Optional[int] = None) -> jax.Array:
    """Run a fused gemm chain as one Pallas kernel.

    ``lhs`` is ``(m, k_0)``; ``rhss[j]`` is stage j's kernel-facing
    ``(k_j, n_j)`` operand (the caller applies the storage transpose —
    gemm stores B as ``(n, k)``); ``biases`` holds one ``(n_j,)`` vector
    per ``has_bias`` stage, in stage order.  ``bm=None`` runs the
    whole-tensor single-phase fast path (``bm = m``).  ``vmem_budget``
    (bytes) raises when the residency estimate exceeds it — the graph
    planner gates on the same estimate and falls back to sequential
    dispatch instead of tripping this.
    """
    m, k0 = lhs.shape
    stages = validate_chain(stages, k0)
    if interleave not in FUSED_INTERLEAVES:
        raise ValueError(f"interleave must be one of {FUSED_INTERLEAVES}, "
                         f"got {interleave!r}")
    if len(rhss) != len(stages):
        raise ValueError(f"{len(stages)} stages need {len(stages)} rhs "
                         f"operands, got {len(rhss)}")
    n_bias = sum(1 for st in stages if st.has_bias)
    if len(biases) != n_bias:
        raise ValueError(f"chain has {n_bias} bias stage(s) but "
                         f"{len(biases)} bias vector(s) were given")
    for j, (st, r) in enumerate(zip(stages, rhss)):
        if tuple(r.shape) != (st.k, st.n):
            raise ValueError(f"stage {j} rhs must be ({st.k}, {st.n}), "
                             f"got {tuple(r.shape)}")
    bm = m if bm is None else max(1, min(int(bm), m))
    out_dtype = jnp.dtype(out_dtype or lhs.dtype)
    if vmem_budget is not None:
        need = chain_vmem_bytes(stages, m, k0, bm, out_dtype.itemsize,
                                interleave)
        if need > vmem_budget:
            raise ValueError(
                f"fused chain needs ~{need} VMEM bytes "
                f"(bm={bm}, interleave={interleave}) but the budget is "
                f"{vmem_budget}; the planner falls back to sequential "
                f"dispatch instead")
    bias_rows = []
    bi = 0
    for st in stages:
        if st.has_bias:
            b = jnp.asarray(biases[bi])
            bi += 1
            if b.shape != (st.n,):
                raise ValueError(f"bias for a (*, {st.n}) stage must be "
                                 f"rank-1 of length {st.n}, got {b.shape}")
            bias_rows.append(b.astype(jnp.float32).reshape(1, st.n))
    return _fused_chain(lhs, *rhss, *bias_rows, stages=stages, bm=bm,
                        interleave=interleave, out_dtype=out_dtype.name,
                        interpret=interpret)


# ---------------------------------------------------------------------------
# DAG megakernel — rhs-landing edges, batched stages, residuals, taps
# ---------------------------------------------------------------------------

#: the DAG template's single interleave order (stage-major, whole-tensor
#: phases); recorded in the tuning cache alongside the chain knobs
DAG_INTERLEAVE = "dag"


@dataclasses.dataclass(frozen=True)
class DagStage:
    """One stage of a fused DAG group (hashable: jit-static + cache-key
    component).  Unlike :class:`ChainStage`, operands are *bound*: each
    source is ``("ext", i)`` (the i-th external kernel operand, already
    in kernel-facing layout) or ``("scr", j)`` (stage j's VMEM scratch).

    * ``kind == "dot"`` — ``out(m, n) = lhs(m, k) @ rhs(k, n)``; a
      scratch-sourced rhs is read **transposed** (the producer's (n, m)
      output lands on this stage's rhs — the rhs-landing fusion), so no
      materialized transpose exists anywhere.
    * ``kind == "batched"`` — the batched_gemv image
      ``out[b, n] = sum_k lhs[b, k, n] * rhs[b, k]`` with the batch axis
      aligned on the group's m axis (PR 4's LoweredForm batch folding,
      merged); ``lhs`` is the external 3-D tensor.

    ``res`` streams a same-shape residual added *after* the epilogue in
    fp32 (the graph's ``add`` node folded in-kernel); ``tap >= 0``
    exports this stage's block to HBM output slot ``tap`` so an unfused
    consumer can read it without re-running the producer.
    """

    m: int
    k: int
    n: int
    kind: str = "dot"                    # "dot" | "batched"
    lhs: Tuple[str, int] = ("ext", 0)
    rhs: Tuple[str, int] = ("ext", 0)
    res: Optional[Tuple[str, int]] = None
    epilogue: Tuple[str, ...] = ()
    has_bias: bool = False
    bias: int = -1                       # ext index of the (1, n) bias row
    tap: int = -1                        # HBM tap output slot (-1: none)


def validate_dag(stages: Sequence[DagStage]) -> Tuple[DagStage, ...]:
    """Validate a DAG stage list: scratch references point backwards with
    chaining shapes, epilogues parse, bias/tap wiring is consistent."""
    stages = tuple(stages)
    if not stages:
        raise ValueError("a fused DAG needs at least one stage")
    taps = []
    for j, st in enumerate(stages):
        if st.kind not in ("dot", "batched"):
            raise ValueError(f"stage {j}: unknown kind {st.kind!r}")
        if st.m <= 0 or st.k <= 0 or st.n <= 0:
            raise ValueError(f"stage {j} has non-positive dims "
                             f"({st.m}, {st.k}, {st.n})")
        for role, src in (("lhs", st.lhs), ("rhs", st.rhs),
                          ("res", st.res)):
            if src is None:
                continue
            where, idx = src
            if where not in ("ext", "scr"):
                raise ValueError(f"stage {j} {role}: bad source {src!r}")
            if where == "scr":
                if not 0 <= idx < j:
                    raise ValueError(f"stage {j} {role} reads scratch "
                                     f"{idx}: must be an earlier stage")
                p = stages[idx]
                want = {"lhs": (st.m, st.k), "res": (st.m, st.n),
                        "rhs": ((st.n, st.k) if st.kind == "dot"
                                else (st.m, st.k))}[role]
                if (p.m, p.n) != want:
                    raise ValueError(
                        f"stage {j} {role} reads stage {idx} "
                        f"({p.m}, {p.n}) but needs {want}")
        if st.kind == "batched" and st.lhs[0] != "ext":
            raise ValueError(f"stage {j}: a batched stage's 3-D tensor "
                             f"must be an external operand")
        spec = _ep.validate_spec(st.epilogue)
        if _ep.needs_bias(spec) != st.has_bias:
            raise ValueError(
                f"stage {j} epilogue {spec} "
                f"{'needs' if _ep.needs_bias(spec) else 'has no'} bias "
                f"but has_bias={st.has_bias}")
        if st.has_bias and st.bias < 0:
            raise ValueError(f"stage {j} has_bias without a bias ext "
                             f"index")
        if st.tap >= 0:
            if j == len(stages) - 1:
                raise ValueError("the final stage is the group result; "
                                 "it cannot also be a tap")
            taps.append(st.tap)
    if sorted(taps) != list(range(len(taps))):
        raise ValueError(f"tap slots must be 0..{len(taps) - 1} with no "
                         f"gaps, got {sorted(taps)}")
    return stages


def dag_scratch_bytes(stages: Sequence[DagStage], itemsize: int) -> int:
    """VMEM scratch of the DAG template: every non-final stage keeps its
    full ``(m, n)`` output resident across the stage-major phases."""
    return sum(st.m * st.n * itemsize for st in tuple(stages)[:-1])


def _dag_fetch(ext, scr, src, transpose=False):
    where, idx = src
    buf = ext[idx][...] if where == "ext" else scr[idx][...]
    return buf.T if transpose else buf


def _dag_kernel(*refs, stages: Tuple[DagStage, ...], n_ext: int,
                n_tap: int, dtype):
    """Stage-major DAG body: grid ``(S,)`` with 'arbitrary' semantics —
    phase ``j`` computes stage ``j`` whole-tensor, reading earlier
    stages' scratch (plain for lhs/res, transposed for a landed rhs)."""
    ext = refs[:n_ext]
    o_ref = refs[n_ext]
    tap_refs = refs[n_ext + 1:n_ext + 1 + n_tap]
    scr = refs[n_ext + 1 + n_tap:]
    s = pl.program_id(0)
    last = len(stages) - 1
    for j, st in enumerate(stages):
        @pl.when(s == j)
        def _run(j=j, st=st):
            if st.kind == "batched":
                a3 = _dag_fetch(ext, scr, st.lhs)       # (m, k, n)
                v = _dag_fetch(ext, scr, st.rhs)        # (m, k)
                acc = mxu_dot(v, a3, (((1,), (1,)), ((0,), (0,))))
            else:
                x = _dag_fetch(ext, scr, st.lhs)
                r = _dag_fetch(ext, scr, st.rhs,
                               transpose=st.rhs[0] == "scr")
                acc = mxu_dot(x, r)
            b_ref = ext[st.bias] if st.has_bias else None
            y = _flush_block(acc, b_ref, st.epilogue, dtype)
            if st.res is not None:
                r_ = _dag_fetch(ext, scr, st.res)
                # external residuals stream in fp32; scratch ones are in
                # the chain dtype — the add itself is always fp32 (the
                # standalone add node's exact math)
                y = (y.astype(jnp.float32)
                     + r_.astype(jnp.float32)).astype(dtype)
            if st.tap >= 0:
                tap_refs[st.tap][...] = y
            if j == last:
                o_ref[...] = y
            else:
                scr[j][...] = y


@functools.partial(
    jax.jit, static_argnames=("stages", "out_dtype", "interpret"))
def _fused_dag(*exts, stages: Tuple[DagStage, ...], out_dtype: str,
               interpret: bool):
    dt = jnp.dtype(out_dtype)
    last = stages[-1]
    n_tap = sum(1 for st in stages if st.tap >= 0)

    def pin(rank):
        return lambda s, _r=rank: (0,) * _r

    in_specs = [pl.BlockSpec(tuple(e.shape), pin(e.ndim)) for e in exts]
    out_shape = [jax.ShapeDtypeStruct((last.m, last.n), dt)]
    out_specs = [pl.BlockSpec((last.m, last.n), pin(2))]
    for st in sorted((s for s in stages if s.tap >= 0),
                     key=lambda s: s.tap):
        out_shape.append(jax.ShapeDtypeStruct((st.m, st.n), dt))
        out_specs.append(pl.BlockSpec((st.m, st.n), pin(2)))
    scratch = [pltpu.VMEM((st.m, st.n), dt) for st in stages[:-1]]
    kernel = functools.partial(_dag_kernel, stages=stages,
                               n_ext=len(exts), n_tap=n_tap, dtype=dt)
    out = pl.pallas_call(
        kernel,
        grid=(len(stages),),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*exts)
    return tuple(out) if isinstance(out, (tuple, list)) else (out,)


def fused_dag(exts: Sequence[jax.Array], *,
              stages: Sequence[DagStage],
              out_dtype=None,
              interpret: bool = False) -> Tuple[jax.Array, ...]:
    """Run a fused DAG group as one Pallas kernel.

    ``exts`` are the external operands in *kernel-facing* layout (the
    caller applies role casts: a landed external rhs is already
    ``(k, n)``, residual streams fp32, bias rows ``(1, n)`` fp32).
    Returns ``(result, *taps)`` — the final stage's output followed by
    the tapped intermediates in tap-slot order.
    """
    stages = validate_dag(stages)
    out_dtype = jnp.dtype(out_dtype or exts[0].dtype)
    return _fused_dag(*exts, stages=stages, out_dtype=out_dtype.name,
                      interpret=interpret)


def dag_reference(exts: Sequence[jax.Array], *,
                  stages: Sequence[DagStage],
                  out_dtype=None) -> Tuple[jax.Array, ...]:
    """Pure-jnp mirror of the DAG megakernel (the ``backend='xla'``
    route): identical per-stage math without the Pallas grid."""
    stages = validate_dag(stages)
    dt = jnp.dtype(out_dtype or exts[0].dtype)
    vals: list = []
    taps: dict = {}
    for j, st in enumerate(stages):
        def fetch(src, transpose=False):
            where, idx = src
            buf = exts[idx] if where == "ext" else vals[idx]
            return buf.T if transpose else buf
        if st.kind == "batched":
            acc = jax.lax.dot_general(
                fetch(st.rhs), fetch(st.lhs),
                (((1,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
        else:
            acc = jnp.dot(fetch(st.lhs),
                          fetch(st.rhs, transpose=st.rhs[0] == "scr"),
                          preferred_element_type=jnp.float32)
        if st.epilogue:
            b = exts[st.bias].reshape(-1) if st.has_bias else None
            acc = _ep.apply_epilogue(acc, st.epilogue, bias=b)
        y = acc.astype(dt)
        if st.res is not None:
            y = (y.astype(jnp.float32)
                 + fetch(st.res).astype(jnp.float32)).astype(dt)
        vals.append(y)
        if st.tap >= 0:
            taps[st.tap] = y
    return (vals[-1],) + tuple(taps[i] for i in sorted(taps))


@functools.partial(jax.jit,
                   static_argnames=("stages", "out_dtype"))
def chain_reference(lhs, *operands, stages: Tuple[ChainStage, ...],
                    out_dtype: str):
    """Pure-jnp mirror of the megakernel (the ``backend='xla'`` route,
    same convention as ``ops.stt_matmul``): identical per-stage math —
    fp32 dot, epilogue, cast — without the Pallas grid."""
    n_stage = len(stages)
    rhss = operands[:n_stage]
    bias_rows = list(operands[n_stage:])
    mid_dtype = lhs.dtype
    x = lhs
    bi = 0
    for j, st in enumerate(stages):
        acc = jnp.dot(x, rhss[j], preferred_element_type=jnp.float32)
        if st.epilogue:
            b = None
            if st.has_bias:
                b = bias_rows[bi]
                bi += 1
            acc = _ep.apply_epilogue(acc, st.epilogue, bias=b)
        x = acc.astype(mid_dtype if j + 1 < n_stage
                       else jnp.dtype(out_dtype))
    return x
