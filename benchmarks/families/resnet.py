"""The bottleneck ResNet: weights and image rows from the seed, the program's
compiled train step (``hvd.DistributedOptimizer`` inside ``trainer.jit_step``,
as ``bench.build_step`` and ``chip_smoke.leg_resnet`` build it), and the plain
reference bound to the same weights. Only ``TrainProgram`` imports the
program."""

from __future__ import annotations

import functools
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmarks.lib import lowprec, train_program, train_reference, trees
from benchmarks.reference import resnet as ref
from benchmarks.roofline import model_flops

NORM = "FoldedBatchNorm"


def weights(config: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """``{"params", "batch_stats"}`` in the tree flax gives the program's
    model, float32: kernels ~ N(0, 2 / fan_in), every norm scale 1 +- 0.1
    (the last norm of a block too, which flax would start at 0: a branch that
    is switched off has no gradient to compare), biases +- 0.1."""
    keys = iter(jax.random.split(key, 400))
    width, expansion = config["num_filters"], config["bottleneck_expansion"]

    def conv(k, c_in, c_out):
        fan_in = k * k * c_in
        return {"kernel": jax.random.normal(
            next(keys), (k, k, c_in, c_out), jnp.float32)
            * (2.0 / fan_in) ** 0.5}

    def norm(c):
        return {"scale": 1.0 + 0.1 * jax.random.normal(
                    next(keys), (c,), jnp.float32),
                "bias": 0.1 * jax.random.normal(
                    next(keys), (c,), jnp.float32)}

    def stats(c):
        return {"mean": jnp.zeros((c,), jnp.float32),
                "var": jnp.ones((c,), jnp.float32)}

    params: Dict[str, Any] = {
        "conv_init": conv(7, config["num_channels"], width),
        "bn_init": norm(width)}
    batch_stats: Dict[str, Any] = {"bn_init": stats(width)}
    c_in, i = width, 0
    for stage, blocks in enumerate(config["stage_sizes"]):
        mid = width * 2 ** stage
        out = mid * expansion
        for j in range(blocks):
            bp = {"Conv_0": conv(1, c_in, mid), NORM + "_0": norm(mid),
                  "Conv_1": conv(3, mid, mid), NORM + "_1": norm(mid),
                  "Conv_2": conv(1, mid, out), NORM + "_2": norm(out)}
            bs = {NORM + "_0": stats(mid), NORM + "_1": stats(mid),
                  NORM + "_2": stats(out)}
            if c_in != out or (stage > 0 and j == 0):
                bp["conv_proj"] = conv(1, c_in, out)
                bp["norm_proj"] = norm(out)
                bs["norm_proj"] = stats(out)
            params[f"BottleneckBlock_{i}"] = bp
            batch_stats[f"BottleneckBlock_{i}"] = bs
            c_in, i = out, i + 1
    params["Dense_0"] = {
        "kernel": jax.random.normal(
            next(keys), (c_in, config["num_classes"]), jnp.float32)
        * c_in ** -0.5,
        "bias": jnp.zeros((config["num_classes"],), jnp.float32)}
    return {"params": params, "batch_stats": batch_stats}


def image_rows(config: Dict[str, Any], key: jax.Array, pool: int, rows: int
               ) -> Tuple[jax.Array, jax.Array]:
    """``pool`` batches of ``rows`` synthetic images (bfloat16, as the input
    pipeline hands them over) and labels; every row differs."""
    k1, k2 = jax.random.split(key)
    size = config["image_size"]
    images = jax.random.normal(
        k1, (pool, rows, size, size, config["num_channels"]), jnp.bfloat16)
    labels = jax.random.randint(k2, (pool, rows), 0, config["num_classes"],
                                jnp.int32)
    return images, labels


class TrainProgram(train_program.TrainProgramBase):
    """The compiled ResNet step with its state."""

    def __init__(self, config: Dict[str, Any], traffic: Dict[str, Any],
                 seed: int, devices: Sequence[Any]):
        import optax

        import horovod_tpu as hvd
        from horovod_tpu import models
        from horovod_tpu.parallel.trainer import jit_step

        self.config, self.traffic, self.seed = config, traffic, seed
        self.devices = list(devices)
        n = len(devices)
        hvd.init(devices=self.devices)
        self._hvd = hvd
        mesh = hvd.mesh()
        axis = mesh.axis_names[0]
        options = dict(traffic.get("program", {}))
        dtype = jnp.dtype(options.pop("dtype", "bfloat16"))
        model = models.ResNet(
            stage_sizes=list(config["stage_sizes"]),
            block_cls=models.resnet.BottleneckBlock,
            num_classes=config["num_classes"],
            num_filters=config["num_filters"], dtype=dtype, **options)
        opt = traffic["optimizer"]
        optimizer = hvd.DistributedOptimizer(
            optax.sgd(opt["lr"], momentum=opt["momentum"]), op=hvd.Average)

        @jit_step
        def step(state, x, y):
            params, batch_stats, opt_state = state

            def loss_fn(p):
                logits, upd = model.apply(
                    {"params": p, "batch_stats": batch_stats}, x, train=True,
                    mutable=["batch_stats"])
                loss = optax.softmax_cross_entropy_with_integer_labels(
                    logits, y).mean()
                return loss, upd["batch_stats"]

            (loss, new_stats), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), new_stats,
                    opt_state), loss

        self.rows = traffic["rows_per_chip"] * n
        self.pool = traffic["batch_pool"]
        repl = NamedSharding(mesh, P())
        key_w, key_b = jax.random.split(trees.key_from_seed(seed))
        variables = jax.jit(functools.partial(weights, config),
                            out_shardings=repl)(key_w)
        size = config["image_size"]
        want = jax.eval_shape(
            model.init, jax.random.PRNGKey(0),
            jnp.zeros((1, size, size, config["num_channels"]), jnp.bfloat16))
        if jax.tree.structure(want) != jax.tree.structure(variables) or any(
                a.shape != b.shape for a, b in zip(
                    jax.tree.leaves(want), jax.tree.leaves(variables))):
            raise RuntimeError("the program's ResNet takes another parameter "
                               "tree than the benchmark draws")
        images, labels = jax.jit(
            lambda k: image_rows(config, k, self.pool, self.rows),
            out_shardings=NamedSharding(mesh, P(None, axis)))(key_b)
        self._batches = [(images[i], labels[i]) for i in range(self.pool)]
        del images, labels
        params = variables["params"]
        self.state = (params, variables["batch_stats"],
                      optimizer.init(params))
        self.compiled = step.lower(self.state, *self._batches[0]).compile()
        self.items_per_step = self.rows
        self.required_flops_per_step = \
            model_flops.resnet_train_flops_per_image(config) * self.rows

    def step(self, k: int) -> jax.Array:
        self.state, loss = self.compiled(
            self.state, *self._batches[k % self.pool])
        return loss

    def params(self):
        return self.state[0]

    def opt_state(self):
        return self.state[2]

    def further(self):
        return self.state[1]            # the running batch statistics

    def release(self) -> None:
        self.state = self._start = self.compiled = self._batches = None
        self._hvd.shutdown()

    def reference(self, ops: lowprec.Ops, steps: int, keep_rows: int = 0
                  ) -> Dict[str, Any]:
        config, traffic = self.config, self.traffic
        if len(self.devices) > 1:
            raise NotImplementedError(
                "batch statistics are per chip: the reference of a "
                "several-chip ResNet cell has to norm each chip's rows apart")
        key_w, key_b = jax.random.split(trees.key_from_seed(self.seed))
        with jax.default_device(self.devices[0]):
            variables = jax.jit(functools.partial(weights, config))(key_w)
            images, labels = jax.jit(lambda k: image_rows(
                config, k, self.pool, self.rows))(key_b)
        opt = traffic["optimizer"]
        block = functools.partial(ref.loss_sum, ops,
                                  tuple(config["stage_sizes"]), NORM)
        return train_reference.readings(
            block, variables["params"],
            [(images[k], labels[k]) for k in range(steps)],
            lr=opt["lr"], momentum=opt["momentum"], devices=self.devices,
            rows_per_block=keep_rows or self.rows, keep_rows=keep_rows,
            further=variables["batch_stats"],
            further_update=jax.jit(ref.running_stats))

