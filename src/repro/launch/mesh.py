"""Production mesh construction.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (the dry-run forces 512 host devices *before* any
jax initialization; tests and benches must keep seeing 1 device).
"""
from __future__ import annotations


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod (v5e pod slice); 2 pods over DCI when multi_pod.

    Axes: ('data', 'model') single-pod; ('pod', 'data', 'model') multi-pod.
    DP runs over (pod, data); FSDP over data; TP/SP/EP over model.
    """
    import jax

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (fake or real) devices exist — used by
    tests and the CPU examples."""
    import jax

    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
