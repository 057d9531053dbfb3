"""BASELINE configs 2-3: ResNet-50 training recipe — the TPU port of
examples/imagenet/main_amp.py (bf16 "amp" + data-parallel + SyncBatchNorm +
FusedAdam over a device mesh; synthetic data stands in for the dataloader).

Run (any host):
  PYTHONPATH=. XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  JAX_PLATFORMS=cpu python examples/imagenet/main_amp.py --tiny
On a TPU slice, drop the env overrides.
"""

import argparse
import functools
import time

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu.models.resnet import ResNet18ish, ResNet50
from apex_tpu.optimizers.functional import adam_update
from apex_tpu.parallel import (bucketed_allreduce, get_mesh,
                               init_distributed)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="small model/images for CPU smoke runs")
    ap.add_argument("--batch-per-device", type=int, default=8)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--lr", type=float, default=1e-3)
    args = ap.parse_args()

    # multi-host rendezvous: honors MASTER_ADDR/RANK/WORLD_SIZE (the
    # torchrun contract of the reference example) and is a no-op for
    # single-process runs
    rank, nproc = init_distributed()
    mesh = get_mesh("data")
    world = mesh.devices.size
    print(f"process {rank}/{nproc}, devices: {world}")

    if args.tiny:
        model = ResNet18ish(num_classes=10, axis_name="data")
        img = (32, 32)
        classes = 10
    else:
        model = ResNet50(num_classes=1000, axis_name="data")
        img = (224, 224)
        classes = 1000

    B = args.batch_per_device * world
    x = jax.random.normal(jax.random.PRNGKey(0), (B, *img, 3))
    y = jax.random.randint(jax.random.PRNGKey(1), (B,), 0, classes)
    variables = model.init(jax.random.PRNGKey(2), x[:2])
    params, bstats = variables["params"], variables["batch_stats"]
    m = jax.tree_util.tree_map(lambda p: jnp.zeros_like(p, jnp.float32),
                               params)
    v = jax.tree_util.tree_map(lambda p: jnp.zeros_like(p, jnp.float32),
                               params)

    def local_step(params, bstats, m, v, xb, yb, step):
        def loss_fn(p):
            logits, mut = model.apply(
                {"params": p, "batch_stats": bstats}, xb,
                mutable=["batch_stats"])
            onehot = jax.nn.one_hot(yb, classes)
            loss = -jnp.mean(jnp.sum(
                jax.nn.log_softmax(logits) * onehot, axis=-1))
            return loss, mut["batch_stats"]

        (loss, new_bstats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        grads = bucketed_allreduce(grads, "data")  # flat-bucket DDP sync
        params, m, v = adam_update(params, grads, m, v, step=step,
                                   lr=args.lr, weight_decay=1e-4)
        return params, new_bstats, m, v, jax.lax.pmean(loss, "data")

    train_step = jax.jit(shard_map(
        local_step, mesh=mesh,
        in_specs=(P(), P(), P(), P(), P("data"), P("data"), P()),
        out_specs=(P(), P(), P(), P(), P()), check_vma=False))

    for step in range(1, args.steps + 1):
        t0 = time.perf_counter()
        params, bstats, m, v, loss = train_step(
            params, bstats, m, v, x, y, jnp.int32(step))
        loss.block_until_ready()
        dt = time.perf_counter() - t0
        print(f"step {step:3d}  loss {float(loss):.4f}  "
              f"{B / dt:8.1f} imgs/s")


if __name__ == "__main__":
    main()
