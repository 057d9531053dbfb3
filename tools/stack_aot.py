"""Deviceless v5e AOT compile of the DISTRIBUTED stack.

Third leg of the AOT evidence tripod (mosaic_aot.py = Pallas kernel zoo,
model_aot.py = single-chip headline models): compiles the multi-chip
training paths against a compile-only 4-device v5e:2x2 client built from
the baked-in libtpu — the ZeRO optimizers (DistributedFusedAdam in all
four state layouts + the 2D redundant grid, DistributedFusedLAMB in both
grad-sync modes and both clip points), the Megatron-style TP×SP GPT-2
train step, the composed 1F1B pipeline + MoE step, and the DDP/SyncBN/
Ulysses shard_map paths. Until now these had only ever compiled for
virtual CPU meshes; this proves the real-TPU lowering (collectives,
layouts, HLO partitioning) with no chip attached.

ZeRO optimizers are instantiated with ``abstract_state=True`` (state as
sharded shape structs — no runtime buffers exist on a compile-only
client). Output: STACK_AOT.json, kept green by tests/test_stack_aot.py.
"""

from __future__ import annotations

import json
import os
import sys
import time

# shared compile-only scaffolding (env + CPU pin + cache) — must import
# before jax backend use
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _aot_common import (ROOT, atomic_write_json,  # noqa: E402
                         get_topology)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import Mesh, NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from jax import shard_map  # noqa: E402

OUT_PATH = os.environ.get("STACK_AOT_OUT",
                          os.path.join(ROOT, "STACK_AOT.json"))

_f32 = jnp.float32


def _params():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    return [jax.random.normal(ks[0], (4096, 128)) * 0.1,
            jax.random.normal(ks[1], (4096,)) * 0.1,
            jax.random.normal(ks[2], (1024, 256)) * 0.1]


def _gstructs(params, sharding=None):
    """Shape structs for grads. By default UNPINNED (no sharding): pinning
    grads replicated at the jit boundary would forbid the partitioner from
    ever emitting the RS+AR mode's reduce-scatter, turning a harness
    artifact into a fake 'modes compile identically' finding."""
    if sharding is None:
        return jax.tree_util.tree_map(
            lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype), params)
    return jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=sharding),
        params)


def compile_dist_adam(mesh, **kw):
    from apex_tpu.optimizers.distributed_fused_adam import \
        DistributedFusedAdam

    params = _params()
    dopt = DistributedFusedAdam(params, mesh, lr=1e-3, weight_decay=0.01,
                                abstract_state=True, **kw)
    jit_tree, _ = dopt._build_step()
    grads = _gstructs(params)
    vecs = dopt._group_vectors(1e-3)
    return jit_tree.lower(dopt._state_pack(), grads, jnp.int32(1),
                          _f32(1.0), jnp.asarray(False), *vecs).compile()


def compile_dist_lamb(mesh, **kw):
    from apex_tpu.optimizers.distributed_fused_lamb import \
        DistributedFusedLAMB

    params = _params()
    dopt = DistributedFusedLAMB(params, mesh, lr=1e-3, weight_decay=0.01,
                                max_grad_norm=1.0, abstract_state=True, **kw)
    jit = dopt._build()
    grads = _gstructs(params)
    return jit.lower(dopt._master, dopt._m, dopt._v, grads, None,
                     jnp.int32(1), _f32(1e-3), _f32(1.0),
                     jnp.asarray(False)).compile()


def compile_gpt2_tp_sp(mesh4):
    from apex_tpu.models.gpt2 import GPT2Config
    from apex_tpu.models.gpt2_parallel import (init_opt_state, init_params,
                                               make_train_step)

    seq = 256
    cfg = GPT2Config(vocab_size=512, n_positions=seq, n_embd=128,
                     n_layer=2, n_head=4)
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt_state = init_opt_state(params)
    step = make_train_step(cfg, mesh4, lr=1e-4)
    tokens = jnp.zeros((2, seq), jnp.int32)
    mask = jnp.ones((2, seq), jnp.float32)
    return step.lower(params, opt_state, tokens, tokens, mask,
                      jnp.int32(1)).compile()


def compile_gpt2_pp_tp(mesh5):
    from apex_tpu.models.gpt2 import GPT2Config
    from apex_tpu.models.gpt2_parallel import (init_opt_state,
                                               init_params_pp,
                                               make_train_step_pp)

    seq = 256
    cfg = GPT2Config(vocab_size=512, n_positions=seq, n_embd=128,
                     n_layer=2, n_head=4)
    p5 = init_params_pp(cfg, jax.random.PRNGKey(7), moe_experts=2)
    st5 = init_opt_state(p5)
    step = make_train_step_pp(cfg, mesh5, lr=1e-4, num_microbatches=2,
                              moe_experts=2)
    tokens = jnp.zeros((2, seq), jnp.int32)
    mask = jnp.ones((2, seq), jnp.float32)
    return step.lower(p5, st5, tokens, tokens, mask, jnp.int32(1)).compile()


def compile_ddp_syncbn(mesh4):
    from apex_tpu.parallel.ddp import bucketed_allreduce
    from apex_tpu.parallel.sync_batch_norm import sync_batch_norm_stats

    def body(grads, x):
        g = bucketed_allreduce(grads, axis_name="data")
        mean, var, cnt = sync_batch_norm_stats(x, (0, 1, 2), "data")
        return g, mean, var, cnt

    ns = NamedSharding(mesh4, P("data"))
    grads = _gstructs(_params(), ns)
    x = jax.ShapeDtypeStruct((8, 8, 8, 64), jnp.float32, sharding=ns)
    fn = jax.jit(shard_map(
        body, mesh=mesh4, in_specs=(P("data"), P("data")),
        out_specs=(P("data"), P(), P(), P()), check_vma=False))
    return fn.lower(grads, x).compile()


def compile_ulysses(mesh4):
    from apex_tpu.parallel.ulysses import ulysses_self_attention

    ns = NamedSharding(mesh4, P(None, None, "data", None))
    q = jax.ShapeDtypeStruct((1, 8, 4 * 512, 64), jnp.bfloat16, sharding=ns)
    fn = jax.jit(shard_map(
        lambda q, k, v: ulysses_self_attention(q, k, v, "data", True),
        mesh=mesh4, in_specs=P(None, None, "data", None),
        out_specs=P(None, None, "data", None), check_vma=False))
    return fn.lower(q, q, q).compile()


def compile_ring_long(mesh16, zigzag: bool):
    """Long-context story at real scale: 131k tokens of causal ring /
    zigzag attention sharded over a 16-chip, 4-HOST v5e:4x4 topology —
    the multi-host partitioning path the reference reaches with NCCL."""
    from apex_tpu.parallel.ring_attention import (
        ring_attention, zigzag_ring_self_attention)

    n = mesh16.shape["sp"]
    s_total = n * 8192  # 131072 tokens over 16 chips
    ns = NamedSharding(mesh16, P(None, None, "sp", None))
    q = jax.ShapeDtypeStruct((1, 8, s_total, 128), jnp.bfloat16,
                             sharding=ns)
    if zigzag:
        body = lambda q, k, v: zigzag_ring_self_attention(  # noqa: E731
            q, k, v, "sp")
    else:
        body = lambda q, k, v: ring_attention(  # noqa: E731
            q, k, v, axis_name="sp", causal=True)
    fn = jax.jit(shard_map(
        body, mesh=mesh16, in_specs=P(None, None, "sp", None),
        out_specs=P(None, None, "sp", None), check_vma=False))
    return fn.lower(q, q, q).compile()


def compile_zero_adam_16dev(mesh16d):
    """ZeRO-2 Adam sharded over 16 chips / 4 hosts at 64M params."""
    from apex_tpu.optimizers.distributed_fused_adam import \
        DistributedFusedAdam

    params = [jnp.zeros((8192, 4096), jnp.float32),
              jnp.zeros((8192 * 4096,), jnp.float32)]
    dopt = DistributedFusedAdam(params, mesh16d, lr=1e-3,
                                store_param_remainders=True,
                                abstract_state=True)
    jit_tree, _ = dopt._build_step()
    grads = _gstructs(params)
    vecs = dopt._group_vectors(1e-3)
    return jit_tree.lower(dopt._state_pack(), grads, jnp.int32(1),
                          _f32(1.0), jnp.asarray(False), *vecs).compile()


def main():
    t0 = time.time()
    topo = get_topology()
    devs = np.array(topo.devices[:4])
    mesh_data = Mesh(devs.reshape(4), ("data",))
    mesh_2d = Mesh(devs.reshape(2, 2), ("data", "rep"))
    from apex_tpu.parallel.mesh import make_mesh

    mesh_tp_sp = make_mesh([1, 2, 2], ["dp", "tp", "sp"], list(devs))
    mesh5 = make_mesh([1, 2, 2, 1, 1], ["dp", "pp", "tp", "sp", "ep"],
                      list(devs))
    # 16-chip, 4-HOST topology for the long-context / ZeRO-at-scale cases
    topo16 = topologies.get_topology_desc("v5e:4x4", "tpu")
    devs16 = np.array(topo16.devices)
    mesh16_sp = Mesh(devs16.reshape(16), ("sp",))
    mesh16_d = Mesh(devs16.reshape(16), ("data",))

    CASES = [
        ("dist_adam_base", lambda: compile_dist_adam(mesh_data)),
        ("dist_adam_param_remainders",
         lambda: compile_dist_adam(mesh_data,
                                   store_param_remainders=True)),
        ("dist_adam_scaled_states",
         lambda: compile_dist_adam(mesh_data, with_scaled_states=True)),
        ("dist_adam_grad_clip",
         lambda: compile_dist_adam(mesh_data, max_grad_norm=1.0)),
        ("dist_adam_2d_redundant",
         lambda: compile_dist_adam(mesh_2d, redundant_axis="rep")),
        ("dist_lamb_rs_ar", lambda: compile_dist_lamb(mesh_data)),
        ("dist_lamb_full_ar",
         lambda: compile_dist_lamb(mesh_data, full_ar=True)),
        ("dist_lamb_clip_before_ar",
         lambda: compile_dist_lamb(mesh_data, clip_after_ar=False)),
        ("gpt2_tp2_sp2_train", lambda: compile_gpt2_tp_sp(mesh_tp_sp)),
        ("gpt2_pp2_tp2_moe_train", lambda: compile_gpt2_pp_tp(mesh5)),
        ("ddp_syncbn_4dev", lambda: compile_ddp_syncbn(mesh_data)),
        ("ulysses_attention_4dev", lambda: compile_ulysses(mesh_data)),
        ("ring_attention_131k_16dev_4host",
         lambda: compile_ring_long(mesh16_sp, zigzag=False)),
        ("zigzag_attention_131k_16dev_4host",
         lambda: compile_ring_long(mesh16_sp, zigzag=True)),
        ("zero_adam_64m_16dev_4host",
         lambda: compile_zero_adam_16dev(mesh16_d)),
    ]

    result = {"device_kind": getattr(topo.devices[0], "device_kind", "?"),
              "jax": jax.__version__,
              "captured": time.strftime("%Y-%m-%dT%H:%M:%S"),
              "cases": {}}
    ok_all = True
    for name, fn in CASES:
        t1 = time.time()
        try:
            compiled = fn()
            entry = {"ok": True}
            try:
                import re

                txt = compiled.as_text()
                # definition sites only: "op(" / "op-start(" — plain
                # substring counts would also hit operand references
                # (%all-gather.5) and double-count async pairs
                entry["collectives"] = {
                    op: len(re.findall(op + r"(?:-start)?\(", txt)) for op in
                    ("all-reduce", "reduce-scatter", "all-gather",
                     "collective-permute", "all-to-all")}
            except Exception:
                pass
        except Exception as e:
            entry = {"ok": False,
                     "error": f"{type(e).__name__}: {str(e)[:1200]}"}
            ok_all = False
        entry["wall_s"] = round(time.time() - t1, 1)
        result["cases"][name] = entry
        print(f"[stack_aot] {name} "
              f"{'OK' if entry['ok'] else 'FAIL ' + entry.get('error', '')}"
              f" ({entry['wall_s']}s)", file=sys.stderr, flush=True)
        result["ok"] = False
        result["wall_s"] = round(time.time() - t0, 1)
        atomic_write_json(OUT_PATH, result)
    result["ok"] = ok_all
    result["wall_s"] = round(time.time() - t0, 1)
    atomic_write_json(OUT_PATH, result)
    print(json.dumps({"ok": ok_all, "cases": len(CASES),
                      "wall_s": result["wall_s"]}))
    sys.exit(0 if ok_all else 2)


if __name__ == "__main__":
    main()
