"""The dense transformer LM: weights and token rows from the seed, the
program's compiled train step and serving engine, and the plain reference
bound to the same weights. Only the ``build_*`` functions import the program.

Configuration keys follow the model's public ``config.json``; the program's
``TransformerConfig`` fields are filled from them."""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmarks.lib import lowprec, train_program, train_reference, trees
from benchmarks.reference import transformer_lm as ref
from benchmarks.roofline import model_flops

STACKED = ("layers",)


def head_dim(config: Dict[str, Any]) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def weights(config: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """The parameter tree the program's transformer takes, float32 as it is
    trained and served: products ~ N(0, 1/fan_in), norm scales 1 +- 0.1 so
    that no leaf is degenerate. The benchmark's own draw, not the program's
    ``init_params``: the reference takes nothing the program made."""
    d, f, v, l = (config["hidden_size"], config["intermediate_size"],
                  config["vocab_size"], config["num_hidden_layers"])
    a = config["num_attention_heads"] * head_dim(config)
    k = iter(jax.random.split(key, 11))

    def dense(shape, fan_in):
        return jax.random.normal(next(k), shape, jnp.float32) * fan_in ** -0.5

    def scale(shape):
        return 1.0 + 0.1 * jax.random.normal(next(k), shape, jnp.float32)

    return {
        "embed": dense((v, d), d),
        "final_norm": scale((d,)),
        "head": dense((d, v), d),
        "layers": {
            "attn_norm": scale((l, d)), "mlp_norm": scale((l, d)),
            "wq": dense((l, d, a), d), "wk": dense((l, d, a), d),
            "wv": dense((l, d, a), d), "wo": dense((l, a, d), a),
            "w_in": dense((l, d, f), d), "w_out": dense((l, f, d), f),
        },
    }


def token_rows(config: Dict[str, Any], key: jax.Array, pool: int, rows: int,
               seq: int) -> Tuple[jax.Array, jax.Array]:
    """``pool`` batches of ``rows`` packed random sequences: tokens and the
    next-token labels, [pool, rows, seq] each. Every row differs."""
    ids = jax.random.randint(key, (pool, rows, seq + 1), 0,
                             config["vocab_size"], jnp.int32)
    return ids[..., :-1], ids[..., 1:]


def program_config(config: Dict[str, Any], **kw):
    from horovod_tpu.models import TransformerConfig
    return TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"], head_dim=head_dim(config),
        n_layers=config["num_hidden_layers"],
        d_ff=config["intermediate_size"],
        max_seq=config["max_position_embeddings"], dtype=jnp.bfloat16, **kw)


class TrainProgram(train_program.TrainProgramBase):
    """The compiled LM step with its state: the one object that set-up
    drives through its first steps and the window then takes over."""

    stacked = STACKED

    def __init__(self, config: Dict[str, Any], traffic: Dict[str, Any],
                 seed: int, devices: Sequence[Any]):
        import optax

        import horovod_tpu as hvd
        from horovod_tpu.parallel import trainer

        self.config, self.traffic, self.seed = config, traffic, seed
        self.devices = list(devices)
        n = len(devices)
        hvd.init(devices=self.devices)
        self._hvd = hvd
        mesh = hvd.mesh()
        axis = mesh.axis_names[0]
        step_kw = dict(traffic.get("program", {}))
        if step_kw.get("scan_unroll") == "all":
            step_kw["scan_unroll"] = config["num_hidden_layers"]
        cfg = program_config(config, dp_axis=axis, **step_kw)
        opt = traffic["optimizer"]
        optimizer = optax.sgd(opt["lr"], momentum=opt["momentum"])
        _, train_step = trainer.make_transformer_train_step(
            cfg, optimizer, mesh)
        self.rows = traffic["rows_per_chip"] * n
        self.seq = traffic["seq_len"]
        self.pool = traffic["batch_pool"]
        repl = NamedSharding(mesh, P())
        key_w, key_b = jax.random.split(trees.key_from_seed(seed))
        params = jax.jit(functools.partial(weights, config),
                         out_shardings=repl)(key_w)
        self._tokens, self._labels = jax.jit(
            lambda k: token_rows(config, k, self.pool, self.rows, self.seq),
            out_shardings=NamedSharding(mesh, P(None, axis)))(key_b)
        self._batches = [(self._tokens[i], self._labels[i])
                         for i in range(self.pool)]
        self.state = trainer.TrainState(
            jnp.zeros((), jnp.int32), params, optimizer.init(params))
        # one AOT compile; the window dispatches this executable
        self.compiled = train_step.lower(
            self.state, *self._batches[0]).compile()
        self.items_per_step = self.rows * self.seq
        self.required_flops_per_step = model_flops.lm_train_flops_per_token(
            config, self.seq) * self.items_per_step

    # -- the window's call and feed ------------------------------------------
    def step(self, k: int) -> jax.Array:
        self.state, loss = self.compiled(
            self.state, *self._batches[k % self.pool])
        return loss

    def params(self):
        return self.state.params

    def opt_state(self):
        return self.state.opt_state

    def facts(self) -> Dict[str, Any]:
        """Shapes of one attention call of the train step, per chip."""
        config, traffic = self.config, self.traffic
        return {"shapes": {
            "batch": traffic["rows_per_chip"], "seq": traffic["seq_len"],
            "heads": config["num_attention_heads"],
            "head_dim": head_dim(config),
            "layers": config["num_hidden_layers"]}}

    def release(self) -> None:
        self.state = self._start = self.compiled = None
        self._batches = self._tokens = self._labels = None
        self._hvd.shutdown()

    # -- the plain reference on the same weights and rows --------------------
    def reference(self, ops: lowprec.Ops, steps: int, keep_rows: int = 0
                  ) -> Dict[str, Any]:
        return reference_readings(self.config, self.traffic, self.seed,
                                  self.devices, ops, steps, keep_rows)


def reference_readings(config, traffic, seed, devices, ops, steps,
                       keep_rows=0) -> Dict[str, Any]:
    rows = traffic["rows_per_chip"] * len(devices)
    if keep_rows:
        devices = devices[:1]       # a planted fault is read on one chip
    n = len(devices)
    key_w, key_b = jax.random.split(trees.key_from_seed(seed))
    with jax.default_device(devices[0]):
        params = jax.jit(functools.partial(weights, config))(key_w)
        tokens, labels = jax.jit(lambda k: token_rows(
            config, k, traffic["batch_pool"], rows, traffic["seq_len"]))(
                key_b)
    if n > 1:
        from jax.sharding import Mesh
        repl = NamedSharding(Mesh(np.array(devices), ("d",)), P())
        params = jax.device_put(params, repl)
        tokens, labels = (jax.device_put(x, repl) for x in (tokens, labels))
    opt = traffic["optimizer"]
    block = functools.partial(ref.loss_sum, ops, head_dim(config))
    return train_reference.readings(
        block, params, [(tokens[k], labels[k]) for k in range(steps)],
        lr=opt["lr"], momentum=opt["momentum"], devices=devices,
        rows_per_block=traffic["reference_rows_per_block"],
        stacked=STACKED, keep_rows=keep_rows)


class ServeProgram:
    """The program's serving engine and scheduler on weights from the seed,
    with the benchmark's own counting around the engine's two device calls.
    For the record the traffic kind reads ``vocab`` (the ids the clients
    draw), ``facts()``, ``hlo_texts()`` and ``counters()``, as of a
    ``TrainProgram`` (``benchmarks/lib/train_program.py``)."""

    main_program = "serve_decode"   # whose text ``rec.program["hlo_text"]`` is

    def __init__(self, config: Dict[str, Any], traffic: Dict[str, Any],
                 seed: int, devices: Sequence[Any], spans):
        from horovod_tpu.serving import Request, ServeEngine, ServeScheduler

        self.config, self.traffic, self.seed = config, traffic, seed
        self.devices = list(devices)
        self.vocab = config["vocab_size"]
        self.Request = Request
        cfg = program_config(config, dp_axis=None)
        with jax.default_device(self.devices[0]):
            params = jax.jit(functools.partial(weights, config))(
                jax.random.split(trees.key_from_seed(seed))[0])
        eng = traffic["engine"]
        self.engine = ServeEngine(
            cfg, params, None, slots=eng["slots"], page=eng["page"],
            max_seq=eng["max_seq"], prefill_chunk=eng["prefill_chunk"],
            prefix_cache=eng["prefix_cache"], draft="off")
        del params
        self.scheduler = ServeScheduler(self.engine)
        self.decode_s: List[float] = []         # host time of each decode step
        self.decode_keys: List[List[int]] = []  # cached keys per slot in use
        self.prefill_tokens = 0
        self.required_flops = 0.0
        self._wrap(spans)

    def _wrap(self, spans) -> None:
        engine, config = self.engine, self.config
        decode, prefill = engine.decode_step, engine.prefill_chunk
        import time

        def decode_step(tokens, active=None):
            lengths = engine.tables.lengths
            keys = [int(n) + 1 for n in (lengths[active] if active is not None
                                         else lengths[lengths > 0])]
            t0 = time.perf_counter()
            with spans.span("bench.decode"):
                out = decode(tokens, active=active)
            self.decode_s.append(time.perf_counter() - t0)
            self.decode_keys.append(keys)
            self.required_flops += sum(
                model_flops.lm_forward_flops(config, 1, n - 1) for n in keys)
            return out

        def prefill_chunk(slot, prompt, start):
            with spans.span("bench.prefill"):
                nxt, first = prefill(slot, prompt, start)
            self.prefill_tokens += nxt - start
            self.required_flops += model_flops.lm_forward_flops(
                config, nxt - start, start,
                logit_rows=0 if first is None else 1)
            return nxt, first

        engine.decode_step, engine.prefill_chunk = decode_step, prefill_chunk

    def request(self, rid: int, prompt: np.ndarray, max_new: int):
        return self.Request(rid=rid, prompt=prompt, max_new_tokens=max_new)

    def facts(self) -> Dict[str, Any]:
        """What the paged-decode roofline needs of the model."""
        return {"heads": self.config["num_attention_heads"],
                "head_dim": head_dim(self.config),
                "layers": self.config["num_hidden_layers"]}

    def hlo_texts(self) -> Dict[str, str]:
        """The decode program and one prefill program per bucket."""
        return {label: self.engine.executable_text(label)
                for label in self.engine.store_outcomes}

    def counters(self) -> Dict[str, Any]:
        return {"decode_keys": self.decode_keys,
                "prefill_tokens": self.prefill_tokens,
                "required_flops": self.required_flops}

    def release(self) -> None:
        self.engine = self.scheduler = None
        from horovod_tpu import serving
        serving.reset_for_tests()       # the module registry holds the engine

    def reference_gaps(self, ops: lowprec.Ops, served: List[Tuple[np.ndarray,
                       List[int]]], pad_to: int) -> List[np.ndarray]:
        return served_token_gaps(self.config, self.seed, self.devices[0],
                                 ops, served, pad_to)


def served_token_gaps(config, seed, device, ops, served, pad_to,
                      against: lowprec.Ops = None) -> List[np.ndarray]:
    """For each (prompt, served tokens): at every served position, how far
    the judged token's float32-reference logit lies below the reference's
    best. The judged token is the served one, or with ``against`` set (the
    control) the one that precision puts first at that position. One forward
    pass over prompt + tokens, padded to ``pad_to``; causal, so the padding
    changes nothing before it."""
    with jax.default_device(device):
        params = jax.jit(functools.partial(weights, config))(
            jax.random.split(trees.key_from_seed(seed))[0])

        def rows_logits(o):
            return jax.jit(lambda p, t, r: ref.logits(
                o, head_dim(config), p, t, r))

        f32 = rows_logits(ops)
        low = rows_logits(against) if against is not None else None
        out = []
        for prompt, tokens in served:
            n, m = len(prompt), len(tokens)
            seq = np.zeros((pad_to,), np.int32)
            seq[:n] = prompt
            seq[n:n + m - 1] = tokens[:-1]
            # token i was produced from position n - 1 + i
            rows = np.full((pad_to,), n - 1, np.int32)
            rows[:m] = n - 1 + np.arange(m)
            lg = np.asarray(f32(params, jnp.asarray(seq), jnp.asarray(rows)))[:m]
            judged = np.asarray(tokens) if low is None else np.argmax(
                np.asarray(low(params, jnp.asarray(seq), jnp.asarray(rows)))[:m],
                axis=-1)
            out.append(lg.max(axis=-1) - lg[np.arange(m), judged])
    return out
