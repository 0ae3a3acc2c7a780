"""Hand-written classic GEMM schedules — kept as test oracles.

Production mesh execution goes through the generic CommPlan interpreter
(``comm_engine.compile_comm_plan``, what ``repro.generate(...).sharded``
runs); these three hand-written schedules survive because they are
independently-derived realizations of the classic algorithms the
interpreter must recover as special cases:

    summa_matmul        = what gemm x MMT must compute
    cannon_matmul       = what gemm x SST must compute
    ring_reduce_matmul  = what gemm x a K-spatial STT must compute

``repro.dist.comm_selftest`` asserts that parity on fake devices.  Mesh
axes are ("x", "y") — the chip-level analogue of the paper's 2-D PE
array.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def square_submesh(n: int = 2) -> Mesh:
    """An (n, n) mesh over the first n*n devices (Cannon needs square)."""
    devs = np.asarray(jax.devices()[:n * n]).reshape(n, n)
    return Mesh(devs, ("x", "y"))


def summa_matmul(a: jax.Array, b: jax.Array, mesh: Mesh) -> jax.Array:
    """SUMMA (MMT-class: inputs all_gather, output sharded/stationary).

    Both operands are fully sharded over the mesh; each (i, j) chip
    all_gathers A's row panel along y and B's column panel along x —
    the mesh realization of the multicast wires — then computes its
    resident C block with zero further communication.
    """
    def body(a_blk, b_blk):
        a_row = jax.lax.all_gather(a_blk, "y", axis=1, tiled=True)
        b_col = jax.lax.all_gather(b_blk, "x", axis=0, tiled=True)
        return jnp.dot(a_row, b_col, preferred_element_type=jnp.float32
                       ).astype(a_blk.dtype)

    return jax.shard_map(
        body, mesh=mesh, in_specs=(P("x", "y"), P("x", "y")),
        out_specs=P("x", "y"))(a, b)


def ring_reduce_matmul(a: jax.Array, b: jax.Array, mesh: Mesh) -> jax.Array:
    """Reduction-class schedule (K spatial: output psum, operands sharded).

    The contraction dimension is sharded over the whole mesh; every chip
    computes a full-size partial product and the reduction tree becomes a
    single psum over both axes.
    """
    def body(a_blk, b_blk):
        partial = jnp.dot(a_blk, b_blk, preferred_element_type=jnp.float32)
        return jax.lax.psum(partial, ("x", "y")).astype(a_blk.dtype)

    return jax.shard_map(
        body, mesh=mesh, in_specs=(P(None, ("x", "y")), P(("x", "y"), None)),
        out_specs=P(None, None))(a, b)


def _skew_blocks(m: jax.Array, s: int, axis: int, by_axis: int) -> jax.Array:
    """Cannon's initial alignment: roll block row/col ``i`` by ``i`` blocks
    (done on the global array; the steady-state rotation is the systolic
    ppermute ring inside the shard_map)."""
    blocks = np.split(np.asarray(m), s, axis=by_axis)
    rolled = [np.roll(blk, -i * (m.shape[axis] // s), axis=axis)
              for i, blk in enumerate(blocks)]
    return jnp.asarray(np.concatenate(rolled, axis=by_axis))


def cannon_matmul(a: jax.Array, b: jax.Array, mesh: Mesh) -> jax.Array:
    """Cannon (SST-class: inputs on ppermute rings, output stationary).

    Blocks of A circulate left along x-rows and blocks of B circulate up
    along y-columns — the chip-mesh realization of the systolic
    nearest-neighbour wires — while each chip's C block stays resident.
    """
    s = mesh.devices.shape[0]
    assert mesh.devices.shape == (s, s), "Cannon needs a square mesh"
    a = _skew_blocks(a, s, axis=1, by_axis=0)   # row i left by i blocks
    b = _skew_blocks(b, s, axis=0, by_axis=1)   # col j up by j blocks
    left = [(j, (j - 1) % s) for j in range(s)]
    up = [(i, (i - 1) % s) for i in range(s)]

    def body(a_blk, b_blk):
        def step(t, carry):
            a_c, b_c, acc = carry
            acc = acc + jnp.dot(a_c, b_c,
                                preferred_element_type=jnp.float32)
            a_c = jax.lax.ppermute(a_c, "y", left)
            b_c = jax.lax.ppermute(b_c, "x", up)
            return a_c, b_c, acc

        acc = jnp.zeros((a_blk.shape[0], b_blk.shape[1]), jnp.float32)
        _, _, acc = jax.lax.fori_loop(0, s, step, (a_blk, b_blk, acc))
        return acc.astype(a_blk.dtype)

    return jax.shard_map(
        body, mesh=mesh, in_specs=(P("x", "y"), P("x", "y")),
        out_specs=P("x", "y"), check_vma=False)(a, b)
