"""One chip's share of the gated DeltaNet hybrid stack (Olmo Hybrid's layer:
gated DeltaNet layers with one decay a head, one full attention layer with
q/k norms among every four, a dense SwiGLU after each, every sublayer normed
after it): weights from the seed, the program's serving engine on them, and
the plain reference bound to the same weights. Only ``program_config`` and
``ServeProgram`` import the program.

Configuration keys follow the model's public ``config.json``. The file is
cut: ``num_hidden_layers`` and ``layer_types`` what this chip holds (one
pipeline stage), every width and the whole vocabulary as published.

Weights are bfloat16 on the device: drawn in blocks of ``BLOCK`` numbers in
float32 and cast inside one compiled call a leaf (one normal of a stacked
leaf's shape costs the TPU compiler seconds a leaf, PERF.md section 7). The
reference draws each stacked leaf again with the same call and takes its
layer out (float32 weights of the whole cut are 9.7 GB)."""

from __future__ import annotations

import functools
import math
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.lib import lowprec, trees
from benchmarks.reference import olmo_hybrid as ref
from benchmarks.roofline import olmo_hybrid as cost

GDN, ATTENTION = ref.GDN, ref.ATTENTION
# the configuration file's ``assumed.weights``: the embedding's rows N(0, 1);
# the decay's input W_a at 1/8 of its fan-in deviation, so the step is
# dt_bias's and not a random product's
EMBED_DEVIATION = 1.0
GAINS: Dict[str, float] = {"['w_a']": 0.125}
BLOCK = (1024, 8192)        # numbers one compiled draw makes at a time


def shapes(config: Dict[str, Any]) -> Dict[str, Any]:
    """(shape, fan-in) of every leaf of the parameter tree the program
    takes; fan-in None for a leaf that is no product's weight. The
    benchmark's own table, not the program's: the reference takes nothing
    the program made (a test holds the two trees to the same shapes)."""
    d, f = config["hidden_size"], config["intermediate_size"]
    w = cost.widths(config)
    layers = cost.layer_counts(config)
    lg, la, l = layers["gdn"], layers["attention"], layers["all"]
    hq = config["num_attention_heads"] * (d // config["num_attention_heads"])
    hkv = config["num_key_value_heads"] * (d // config["num_attention_heads"])
    h, vw = w["heads"], w["value"]
    gdn = {"norm": ((lg, d), None), "w_qkv": ((lg, d, w["conv"]), d),
           "conv_w": ((lg, config["linear_conv_kernel_dim"], w["conv"]),
                      None),
           "w_a": ((lg, d, h), d), "w_b": ((lg, d, h), d),
           "A_log": ((lg, h), None), "dt_bias": ((lg, h), None),
           "w_g": ((lg, d, vw), d),
           "o_norm": ((lg, config["linear_value_head_dim"]), None),
           "w_o": ((lg, vw, d), vw)}
    attention = {"norm": ((la, d), None), "wq": ((la, d, hq), d),
                 "wk": ((la, d, hkv), d), "wv": ((la, d, hkv), d),
                 "q_norm": ((la, hq), None), "k_norm": ((la, hkv), None),
                 "wo": ((la, hq, d), hq)}
    mlp = {"norm": ((l, d), None), "w_gate": ((l, d, f), d),
           "w_up": ((l, d, f), d), "w_down": ((l, f, d), f)}
    return {"embed": ((config["vocab_size"], d), d),
            "head": ((config["vocab_size"], d), d),
            "final_norm": ((d,), None),
            "layers": {GDN: gdn, ATTENTION: attention, "mlp": mlp}}


def _is_leaf(x: Any) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def _kind(name: str, fan_in: Optional[int]) -> str:
    """How a leaf is drawn (``_draw``)."""
    for key in ("A_log", "dt_bias", "conv_w"):
        if name.endswith(f"['{key}']"):
            return key
    return "scale" if fan_in is None else "product"


def _leaves(config: Dict[str, Any]
            ) -> Tuple[List[Tuple[str, Tuple, str, Optional[float]]], Any]:
    """(name, shape, kind, the deviation of its draw) of every leaf, in the
    tree's order, and the tree."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes(config), is_leaf=_is_leaf)
    out = []
    for path, (shape, fan_in) in flat:
        name = jax.tree_util.keystr(path)
        kind = _kind(name, fan_in)
        if kind == "conv_w":
            scale = config["linear_conv_kernel_dim"] ** -0.5
        elif fan_in is None:
            scale = None
        elif name == "['embed']":
            scale = EMBED_DEVIATION
        else:
            scale = next((g for end, g in GAINS.items()
                          if name.endswith(end)), 1.0) * fan_in ** -0.5
        out.append((name, shape, kind, scale))
    return out, treedef


def _draw(kind: str, scale: Optional[float], shape: Tuple[int, ...],
          key: jax.Array) -> jax.Array:
    """One leaf, whole. A product ~ N(0, scale^2) (``scale`` = gain /
    sqrt(fan_in); the embedding's rows N(0, 1)) rounded to bfloat16; float32
    the rest: a norm scale 1 +- 0.1, the convolutions N(0, 1 / K), the decay
    as the public gated DeltaNet / Mamba-2 code initialises it (A uniform in
    [1, 16], the step log-uniform in [1e-3, 1e-1] through the inverse
    softplus). The normals in blocks of ``BLOCK`` numbers, each from its own
    key, one after another (``lax.map``)."""
    f32 = jnp.float32
    if kind == "A_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    if kind == "dt_bias":
        step = jnp.exp(jax.random.uniform(
            key, shape, f32, math.log(1e-3), math.log(1e-1)))
        return step + jnp.log(-jnp.expm1(-step))

    def block(k):
        z = jax.random.normal(k, BLOCK, f32)
        if kind == "scale":
            return 1.0 + 0.1 * z
        if kind == "conv_w":
            return z * scale
        return (z * scale).astype(jnp.bfloat16)

    n = math.prod(shape)
    keys = jax.random.split(key, -(-n // (BLOCK[0] * BLOCK[1])))
    return lax.map(block, keys).reshape(-1)[:n].reshape(shape)


# one compiled call a leaf; leaves of one kind, scale and shape share it
_draw_one = jax.jit(_draw, static_argnums=(0, 1, 2))


@jax.jit
def _widened(leaf: jax.Array, at: jax.Array) -> jax.Array:
    """Layer ``at`` of a stacked leaf, float32: what the reference reads."""
    return lax.dynamic_index_in_dim(leaf, at, keepdims=False).astype(
        jnp.float32)


def weights(config: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """The whole tree as the program serves it."""
    leaves, treedef = _leaves(config)
    return jax.tree.unflatten(treedef, [
        _draw_one(kind, scale, shape, jax.random.fold_in(key, i))
        for i, (name, shape, kind, scale) in enumerate(leaves)])


def top_weights(config: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """The embedding, the untied head and the final norm, float32."""
    leaves, _ = _leaves(config)
    return {name[2:-2]: _draw_one(kind, scale, shape,
                                  jax.random.fold_in(key, i)
                                  ).astype(jnp.float32)
            for i, (name, shape, kind, scale) in enumerate(leaves)
            if not name.startswith("['layers']")}


def layer_weights(config: Dict[str, Any], key: jax.Array, layer: int
                  ) -> Tuple[str, Dict[str, Any], Dict[str, Any]]:
    """Layer ``layer`` of the stack, float32: (its kind, its mixer's
    weights, its SwiGLU's) — the values ``weights`` puts at the layer's
    index among its kind of the mixer's stacked leaves and at ``layer`` of
    the SwiGLUs'. Each stacked leaf is drawn whole again (the call
    ``weights`` compiled) and the layer taken out of it."""
    kinds = ref.layer_types_of(config)
    kind = kinds[layer]
    index = {"mlp": layer, kind: kinds[:layer].count(kind)}
    leaves, _ = _leaves(config)
    drawn = {group: [] for group in index}
    for i, (name, shape, how, scale) in enumerate(leaves):
        for group, at in index.items():
            if name.startswith(f"['layers']['{group}']"):
                whole = _draw_one(how, scale, shape,
                                  jax.random.fold_in(key, i))
                drawn[group].append(_widened(whole, jnp.int32(at)))
                del whole
    tree = shapes(config)["layers"]
    return (kind, *(jax.tree.unflatten(
        jax.tree.structure(tree[group], is_leaf=_is_leaf), drawn[group])
        for group in (kind, "mlp")))


def program_config(config: Dict[str, Any], **kw):
    from horovod_tpu.models import OlmoHybridConfig
    ref.dims_of(config)         # refuses the readings not written down
    return OlmoHybridConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        layer_types=ref.layer_types_of(config),
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        d_ff=config["intermediate_size"],
        gdn_n_heads=config["linear_num_key_heads"],
        gdn_d_key=config["linear_key_head_dim"],
        gdn_d_value=config["linear_value_head_dim"],
        gdn_conv=config["linear_conv_kernel_dim"],
        norm_eps=float(config["rms_norm_eps"]),
        max_seq=config["max_position_embeddings"], dtype=jnp.bfloat16, **kw)


class ServeProgram:
    """The program's serving engine and scheduler on weights from the seed,
    with the benchmark's own counting around the engine's two device calls.
    For the record the traffic kind reads ``vocab`` (the ids the clients
    draw: the whole vocabulary), ``facts()``, ``hlo_texts()`` and
    ``counters()``."""

    main_program = "serve_decode"   # whose text ``rec.program["hlo_text"]`` is

    def __init__(self, config: Dict[str, Any], traffic: Dict[str, Any],
                 seed: int, devices: Sequence[Any], spans):
        from horovod_tpu.serving import Request, ServeEngine, ServeScheduler

        self.config, self.traffic, self.seed = config, traffic, seed
        self.devices = list(devices)
        self.vocab = config["vocab_size"]
        self.Request = Request
        cfg = program_config(config)
        with jax.default_device(self.devices[0]):
            params = weights(config, trees.key_from_seed(seed))
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
        self.prefill_chunks = 0
        self.flops = 0.0
        self.prefill_flops = 0.0
        self._wrap(spans)

    def _wrap(self, spans) -> None:
        engine, config = self.engine, self.config
        decode, prefill = engine.decode_step, engine.prefill_chunk

        def decode_step(tokens, active=None):
            lengths = engine.tables.lengths
            keys = [int(n) + 1 for n in (lengths[active] if active is not None
                                         else lengths[lengths > 0])]
            t0 = time.perf_counter()
            with spans.span("bench.decode"):
                out = decode(tokens, active=active)
            self.decode_s.append(time.perf_counter() - t0)
            self.decode_keys.append(keys)
            self.flops += sum(cost.forward_flops(config, 1, n - 1)
                              for n in keys)
            return out

        def prefill_chunk(slot, prompt, start):
            with spans.span("bench.prefill"):
                nxt, first = prefill(slot, prompt, start)
            self.prefill_tokens += nxt - start
            self.prefill_chunks += 1
            flops = cost.forward_flops(config, nxt - start, start,
                                       logit_rows=0 if first is None else 1)
            self.flops += flops
            self.prefill_flops += flops
            return nxt, first

        engine.decode_step, engine.prefill_chunk = decode_step, prefill_chunk

    def request(self, rid: int, prompt: np.ndarray, max_new: int):
        return self.Request(rid=rid, prompt=prompt, max_new_tokens=max_new)

    def facts(self) -> Dict[str, Any]:
        """What the rooflines and the counter readers need of the model: the
        configuration's sizes and the bytes of slot state the engine holds
        (``engine.stats()["ssm"]``), as stored and as resident."""
        ssm = self.engine.stats()["ssm"]
        return {"layers": self.config["num_hidden_layers"],
                "heads": self.config["num_attention_heads"],
                "slots": self.traffic["engine"]["slots"],
                "ssm_state_bytes": ssm["state_bytes"],
                "ssm_resident_bytes": ssm["resident_bytes"],
                "model": {k: v for k, v in self.config.items()
                          if isinstance(v, (int, float, dict, list))
                          and k not in ("assumed", "published")}}

    def hlo_texts(self) -> Dict[str, str]:
        """The decode program and one prefill program per bucket."""
        return {label: self.engine.executable_text(label)
                for label in self.engine.store_outcomes}

    def counters(self) -> Dict[str, Any]:
        """Running totals. The slot-state counters live on the device and
        are read here (``engine.stats()``), before and after a window, never
        inside a step. ``required_flops``: every product of every token
        prefilled and generated; ``prefill_required_flops`` the same of the
        prefill chunks alone."""
        ssm = self.engine.stats()["ssm"]
        return {"decode_keys": self.decode_keys,
                "prefill_tokens": self.prefill_tokens,
                "prefill_chunks": self.prefill_chunks,
                "required_flops": self.flops,
                "prefill_required_flops": self.prefill_flops,
                "ssm_resets": ssm["resets"],
                "ssm_chunks_carried": ssm["chunks_carried"],
                "ssm_decode_rows": ssm["decode_rows"]}

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
    pass over prompt + tokens, padded to ``pad_to`` (causal, so the padding
    changes nothing before it), taken layer by layer: one layer's float32
    weights are drawn, every sequence goes through it, the next is drawn."""
    dims = ref.dims_of(config)
    key = trees.key_from_seed(seed)
    passes = [ops] + ([against] if against is not None else [])
    with jax.default_device(device), jax.default_matmul_precision("highest"):
        top = top_weights(config, key)
        seqs, rows = [], []
        for prompt, tokens in served:
            n, m = len(prompt), len(tokens)
            seq = np.zeros((pad_to,), np.int32)
            seq[:n] = prompt
            seq[n:n + m - 1] = tokens[:-1]
            # token i was produced from position n - 1 + i
            row = np.full((pad_to,), n - 1, np.int32)
            row[:m] = n - 1 + np.arange(m)
            seqs.append(jnp.asarray(seq))
            rows.append(jnp.asarray(row))
        hidden = [[top["embed"][seq] for seq in seqs] for _ in passes]
        layer_of = {(o.name, kind): jax.jit(
            functools.partial(ref.layer, o, dims, kind))
            for o in passes for kind in (GDN, ATTENTION)}
        for l in range(config["num_hidden_layers"]):
            kind, mixer_p, mlp_p = layer_weights(config, key, l)
            hidden = [[layer_of[o.name, kind](h, mixer_p, mlp_p)
                       for h in hs] for o, hs in zip(passes, hidden)]
            jax.block_until_ready(hidden)
            del mixer_p, mlp_p
        head_of = [jax.jit(functools.partial(ref.head_logits, o, dims))
                   for o in passes]
        out = []
        for i, (_, tokens) in enumerate(served):
            m = len(tokens)
            lg = np.asarray(head_of[0](hidden[0][i][rows[i]],
                                       top["final_norm"], top["head"]))[:m]
            judged = np.asarray(tokens) if against is None else np.argmax(
                np.asarray(head_of[1](hidden[1][i][rows[i]],
                                      top["final_norm"], top["head"]))[:m],
                axis=-1)
            out.append(lg.max(axis=-1) - lg[np.arange(m), judged])
    return out
