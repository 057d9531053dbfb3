"""Device timing that amortizes dispatch: K steps in one compiled loop.

JAX dispatch is asynchronous and a single dispatch carries host overhead
comparable to a small kernel, so wall-clock around individual calls
measures the enqueue, not the compute. Here K steps run inside ONE jitted
``lax.fori_loop`` (the TPU analog of the reference's CUDA-graph
"capturable" motivation — amortize launch overhead,
csrc/multi_tensor_adam.cu capturable variants), completion is forced by a
data-dependent host fetch of one element of the result, the measured
fetch floor is subtracted, and the remainder is divided by K.
"""

from __future__ import annotations

import time

import numpy as np


def fetch_scalar(x):
    """Host-fetch a (tiny) array, forcing the producing computation to finish."""
    import jax

    return np.asarray(jax.device_get(x))


def measure_fetch_floor(reps: int = 8) -> float:
    """Seconds of pure dispatch+fetch round-trip for a trivial computation."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def tiny(x):
        return x * 2.0

    for _ in range(3):
        fetch_scalar(tiny(jnp.float32(1.0)))
    t0 = time.perf_counter()
    for i in range(reps):
        fetch_scalar(tiny(jnp.float32(2.0 + i)))
    return (time.perf_counter() - t0) / reps


def timed_steps(step_fn, init_state, iters: int, *, consts=(), witness=None,
                floor_s: float | None = None, donate: bool = True) -> float:
    """Milliseconds per step of ``step_fn`` amortized over ``iters`` chained
    executions inside one compiled loop.

    ``step_fn(i, state, *consts) -> state`` must be jit-traceable with
    matching state structure/dtypes (so the loop carry aliases in place).
    ``consts`` are loop-invariant operands (grads, activations, weights):
    they MUST be passed here rather than closed over — a closed-over device
    array becomes a jaxpr CONSTANT, which (a) is embedded literally in the
    HLO handed to the compiler (a 2 GB grad buffer becomes a 2 GB
    literal in the module and a second copy in HBM) and (b) cannot
    alias or donate. ``witness(state)`` selects a tiny slice to fetch
    (default: first leaf's [0] element). State buffers are donated by
    default so 1B-param-scale benches fit in HBM without loop-entry copies.
    """
    import functools

    import jax

    if floor_s is None:
        floor_s = measure_fetch_floor()

    def default_witness(state):
        leaf = jax.tree_util.tree_leaves(state)[0]
        return leaf.ravel()[0]

    witness = witness or default_witness

    @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
    def many(state, *consts):
        def body(i, s):
            return step_fn(i, s, *consts)
        return jax.lax.fori_loop(0, iters, body, state)

    out = many(init_state, *consts)
    fetch_scalar(witness(out))  # compile + first run
    # regenerate the donated carry from the (finished) previous output:
    # rebinding out -> init keeps one live copy only
    init2 = out
    t0 = time.perf_counter()
    out = many(init2, *consts)
    fetch_scalar(witness(out))
    elapsed = time.perf_counter() - t0
    # floor is measured separately and can exceed a fast run's elapsed time;
    # clamp so consumers dividing by the result never see <= 0
    corrected = max(elapsed - floor_s, 0.05 * elapsed)
    return corrected / iters * 1e3
