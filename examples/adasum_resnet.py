"""ResNet trained with Adasum gradient combination — BASELINE workload 4.

Reference analogue: the Adasum benchmark (examples/adasum/adasum_bench.ipynb)
and ``op=hvd.Adasum`` training (docs/adasum_user_guide.rst; Adasum VHDD
adasum/adasum.h:38,194): gradients are combined pairwise with the
scale-invariant rule a' = (1 - a.b/2|a|^2)a + (1 - a.b/2|b|^2)b instead of
averaged, removing the need for LR rescaling by world size.

TPU-native form: per-shard gradients are computed inside shard_map over the
mesh axis and combined with the XOR-butterfly Adasum composite
(horovod_tpu/ops/adasum.py — ppermute exchanges at power-of-2 distances),
all in one jitted program.

Run:  hvdrun --virtual -np 8 python examples/adasum_resnet.py \
          --model resnet18 --batch-size 4 --num-iters 3
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.eager import shard_map
from horovod_tpu.models import resnet as resnet_lib


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet18",
                    choices=["resnet18", "resnet34", "resnet50"])
    ap.add_argument("--batch-size", type=int, default=8,
                    help="per-chip batch size")
    ap.add_argument("--image-size", type=int, default=64)
    ap.add_argument("--num-iters", type=int, default=5)
    ap.add_argument("--lr", type=float, default=0.05,
                    help="NOT scaled by world size: Adasum's magnitude "
                         "preservation replaces the LR rescale")
    args = ap.parse_args()

    hvd.init()
    size, rank = hvd.size(), hvd.rank()
    mesh = hvd.mesh()
    axis = list(mesh.shape.keys())[0]

    model_cls = {"resnet18": resnet_lib.ResNet18,
                 "resnet34": resnet_lib.ResNet34,
                 "resnet50": resnet_lib.ResNet50}[args.model]
    model = model_cls(num_classes=100, dtype=jnp.float32)

    global_batch = args.batch_size * size
    rng = np.random.RandomState(0)
    images = rng.rand(global_batch, args.image_size, args.image_size,
                      3).astype(np.float32)
    labels = rng.randint(0, 100, size=(global_batch,)).astype(np.int32)

    variables = model.init(jax.random.PRNGKey(0), jnp.asarray(images[:1]),
                           train=False)
    variables = hvd.broadcast_parameters(variables, root_rank=0)

    def loss_fn(p, batch):
        x, y = batch
        logits, _ = model.apply(p, x, train=True, mutable=["batch_stats"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    # Per-shard grads -> Adasum combine across the axis (inside shard_map,
    # the explicit-collective path of distributed_value_and_grad).
    vg = hvd.distributed_value_and_grad(loss_fn, op=hvd.Adasum, axis=axis)
    opt = optax.sgd(args.lr, momentum=0.9)

    def per_shard(p, batch):
        return vg(p, batch)

    grads_fn = jax.jit(shard_map(
        per_shard, mesh, in_specs=(P(), P(axis)), out_specs=(P(), P())))

    @jax.jit
    def apply_update(p, s, grads):
        updates, s = opt.update(grads, s, p)
        return optax.apply_updates(p, updates), s

    opt_state = opt.init(variables)
    from jax.sharding import NamedSharding
    batch = jax.device_put(
        (images, labels), NamedSharding(mesh, P(axis)))

    # A host readback of a scalar that depends on the update closes the
    # timed window exactly.
    def fence(variables):
        return float(jnp.sum(jax.tree.leaves(variables)[0]))

    loss, grads = grads_fn(variables, batch)       # compile + warm
    variables, opt_state = apply_update(variables, opt_state, grads)
    losses = [loss]
    fence(variables)                               # warmup fully done
    t0 = time.perf_counter()
    for i in range(args.num_iters):
        loss, grads = grads_fn(variables, batch)
        variables, opt_state = apply_update(variables, opt_state, grads)
        losses.append(loss)
    fence(variables)                               # includes final update
    dt = time.perf_counter() - t0
    losses = [float(l) for l in losses]

    if rank == 0:
        print(f"adasum {args.model}: losses "
              f"{' '.join(f'{l:.3f}' for l in losses)} "
              f"({args.num_iters * global_batch / dt:.0f} img/s, "
              f"{size} chips)")


if __name__ == "__main__":
    main()
