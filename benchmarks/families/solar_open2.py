"""One chip's share of the delta-rule hybrid stack (Solar Open 2's layer:
gated delta-rule linear-attention layers, one gated grouped-query attention
layer among every few, sigmoid-routed experts plus a shared expert in each):
weights from the seed, the program's serving engine on them, and the plain
reference bound to the same weights. Only ``program_config`` and
``ServeProgram`` import the program.

Configuration keys follow the model's public ``config.json``. The file is
cut: ``n_routed_experts`` counts the routed experts held here (the first of
the published count; the router keeps its published width),
``num_hidden_layers`` / ``gqa_layers`` and ``vocab_size`` what this chip
holds.

Weights are bfloat16 on the device: drawn leaf by leaf in float32 and cast
inside one compiled call, so no float32 stack outlives its leaf. A stacked
leaf's layers have a key each, so the reference draws one layer at a time
(one layer's float32 weights are 3.1 GB, the cut's 13 GB)."""

from __future__ import annotations

import functools
import math
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import lowprec, trees
from benchmarks.reference import solar_open2 as ref
from benchmarks.roofline import solar_open2 as cost

KDA, ATTENTION = ref.KDA, ref.ATTENTION
# the configuration file's ``assumed.weights``: the embedding's rows N(0, 1),
# and, by the end of a leaf's name, the gain (times 1 / sqrt(fan_in)) of the
# products that are not drawn at 1
EMBED_DEVIATION = 1.0
GAINS: Dict[str, float] = {
    # the gates' pre-activations at a deviation of 4 (2 for the decay's): a
    # relative error before a sigmoid then moves its output, which is what
    # tells a lower precision from the stated one
    "['w_g2']": 4.0, "['wz']": 4.0, "['w_b']": 4.0, "['w_f2']": 2.0,
    # a routed expert's output at a quarter of the shared expert's
    # deviation: a flipped eighth choice (equal gates of 1/8 under this rule,
    # whatever the router's gain) is the program's largest own error, and
    # shrinks with it
    "['moe']['w_down']": 0.25}


def held_experts(config: Dict[str, Any]) -> int:
    return config["n_routed_experts"]


def shapes(config: Dict[str, Any]) -> Dict[str, Any]:
    """(shape, fan-in) of every leaf of the parameter tree the program
    takes; fan-in None for a leaf that is no product's weight. The
    benchmark's own table, not the program's: the reference takes nothing
    the program made (a test holds the two trees to the same shapes)."""
    d = config["hidden_size"]
    linear = config["linear_attn_config"]
    h, dh = linear["num_heads"], linear["head_dim"]
    w, r, k = h * dh, dh, linear["short_conv_kernel_size"]
    layers = cost.layer_counts(config)
    lk, la, l = layers["kda"], layers["attention"], layers["all"]
    hq = config["num_attention_heads"] * config["head_dim"]
    hkv = config["num_key_value_heads"] * config["head_dim"]
    e, fe = held_experts(config), config["moe_intermediate_size"]
    fs, n = fe * config["n_shared_experts"], cost.router_width(config)
    kda = {"norm": ((lk, d), None), "w_qkv": ((lk, d, 3 * w), d),
           "conv_w": ((lk, k, 3 * w), None),
           "w_f1": ((lk, d, r), d), "w_f2": ((lk, r, w), r),
           "dt_bias": ((lk, w), None), "A_log": ((lk, h), None),
           "w_b": ((lk, d, h), d),
           "w_g1": ((lk, d, r), d), "w_g2": ((lk, r, w), r),
           "o_norm": ((lk, dh), None), "w_o": ((lk, w, d), w)}
    attention = {"norm": ((la, d), None), "wq": ((la, d, hq), d),
                 "wz": ((la, d, hq), d), "wk": ((la, d, hkv), d),
                 "wv": ((la, d, hkv), d), "wo": ((la, hq, d), hq)}
    moe = {"norm": ((l, d), None), "router": ((l, d, n), d),
           "router_bias": ((l, n), None),
           "w_gate": ((l, e, d, fe), d), "w_up": ((l, e, d, fe), d),
           "w_down": ((l, e, fe, d), fe),
           "shared": {"w_gate": ((l, d, fs), d), "w_up": ((l, d, fs), d),
                      "w_down": ((l, fs, d), fs)}}
    return {"embed": ((config["vocab_size"], d), d),
            "head": ((config["vocab_size"], d), d),
            "final_norm": ((d,), None),
            "layers": {KDA: kda, ATTENTION: attention, "moe": moe}}


def _is_leaf(x: Any) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def _kind(name: str, fan_in: Optional[int]) -> str:
    """How a leaf is drawn (``_draw``)."""
    for key in ("A_log", "dt_bias", "conv_w", "router_bias"):
        if name.endswith(f"['{key}']"):
            return key
    if fan_in is None:
        return "scale"
    return "router" if name.endswith("['router']") else "product"


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
            scale = config["linear_attn_config"]["short_conv_kernel_size"] \
                ** -0.5
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
          wide: bool, key: jax.Array) -> jax.Array:
    """One leaf, or one layer of a stacked leaf. A product ~ N(0, scale^2)
    (``scale`` = gain / sqrt(fan_in); the embedding's rows N(0, 1)) rounded
    to bfloat16, the router rounded likewise and kept in float32 (it is
    served in float32) with a zero selection bias; float32 the rest: a norm
    scale 1 +- 0.1; the decay as the public KDA / Mamba-2 code initialises
    it (A uniform in [1, 16], the step log-uniform in [1e-3, 1e-1] through
    the inverse softplus), the convolutions N(0, 1 / K). ``wide``: the
    bfloat16 values widened to float32, for the reference."""
    f32 = jnp.float32
    if kind == "scale":
        return 1.0 + 0.1 * jax.random.normal(key, shape, f32)
    if kind == "A_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    if kind == "dt_bias":
        step = jnp.exp(jax.random.uniform(
            key, shape, f32, math.log(1e-3), math.log(1e-1)))
        return step + jnp.log(-jnp.expm1(-step))
    if kind == "conv_w":
        return jax.random.normal(key, shape, f32) * scale
    if kind == "router_bias":
        return jnp.zeros(shape, f32)
    w = (jax.random.normal(key, shape, f32) * scale).astype(jnp.bfloat16)
    return w.astype(f32) if (wide or kind == "router") else w


# one compiled call a leaf; leaves of one kind and shape share it
_draw_one = jax.jit(_draw, static_argnums=(0, 1, 2, 3))
_draw_layers = jax.jit(
    lambda kind, scale, shape, wide, keys: jax.vmap(
        functools.partial(_draw, kind, scale, shape, wide))(keys),
    static_argnums=(0, 1, 2, 3))


def _layer_keys(key: jax.Array, leaf: int, layers: int) -> jax.Array:
    return jax.random.split(jax.random.fold_in(key, leaf), layers)


def weights(config: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """The whole tree as the program serves it."""
    leaves, treedef = _leaves(config)
    out = []
    for i, (name, shape, kind, scale) in enumerate(leaves):
        if name.startswith("['layers']"):
            out.append(_draw_layers(kind, scale, shape[1:], False,
                                    _layer_keys(key, i, shape[0])))
        else:
            out.append(_draw_one(kind, scale, shape, False,
                                 jax.random.fold_in(key, i)))
    return jax.tree.unflatten(treedef, out)


def top_weights(config: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """The embedding, the untied head and the final norm, float32."""
    leaves, _ = _leaves(config)
    return {name[2:-2]: _draw_one(kind, scale, shape, True,
                                  jax.random.fold_in(key, i))
            for i, (name, shape, kind, scale) in enumerate(leaves)
            if not name.startswith("['layers']")}


def layer_weights(config: Dict[str, Any], key: jax.Array, layer: int
                  ) -> Tuple[str, Dict[str, Any], Dict[str, Any]]:
    """Layer ``layer`` of the stack, float32: (its kind, its mixer's
    weights, its expert block's) — the values ``weights`` puts at the
    layer's index among its kind of the mixer's stacked leaves and at
    ``layer`` of the expert block's."""
    kinds = ref.layer_types_of(config)
    kind = kinds[layer]
    index = {"moe": layer, kind: kinds[:layer].count(kind)}
    leaves, _ = _leaves(config)
    drawn = {group: [] for group in index}
    for i, (name, shape, how, scale) in enumerate(leaves):
        for group, at in index.items():
            if name.startswith(f"['layers']['{group}']"):
                drawn[group].append(_draw_one(
                    how, scale, shape[1:], True,
                    _layer_keys(key, i, shape[0])[at]))
    tree = shapes(config)["layers"]
    return (kind, *(jax.tree.unflatten(
        jax.tree.structure(tree[group], is_leaf=_is_leaf), drawn[group])
        for group in (kind, "moe")))


def program_config(config: Dict[str, Any], **kw):
    from horovod_tpu.models import SolarOpen2Config
    ref.dims_of(config)         # refuses the switches not written down
    linear = config["linear_attn_config"]
    n = config["num_hidden_layers"]
    return SolarOpen2Config(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers_total=n,
        gqa_layers=tuple(i for i in config["gqa_layers"] if i < n),
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], kda_n_heads=linear["num_heads"],
        kda_head_dim=linear["head_dim"],
        kda_conv=linear["short_conv_kernel_size"],
        n_routed_experts=cost.router_width(config),
        top_k=config["num_experts_per_tok"],
        routed_scaling=float(config["routed_scaling_factor"]),
        d_expert=config["moe_intermediate_size"],
        d_shared=config["moe_intermediate_size"]
        * config["n_shared_experts"],
        expert_first=0, expert_count=held_experts(config),
        norm_eps=float(config["rms_norm_eps"]),
        max_seq=config["max_position_embeddings"], dtype=jnp.bfloat16, **kw)


class ServeProgram:
    """The program's serving engine and scheduler on weights from the seed,
    with the benchmark's own counting around the engine's two device calls.
    For the record the traffic kind reads ``vocab`` (the ids the clients
    draw: the slice of the vocabulary held here), ``facts()``,
    ``hlo_texts()`` and ``counters()``."""

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
        self.flops_outside_experts = 0.0
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
            self.flops_outside_experts += sum(
                cost.forward_flops(config, 1, n - 1) for n in keys)
            return out

        def prefill_chunk(slot, prompt, start):
            with spans.span("bench.prefill"):
                nxt, first = prefill(slot, prompt, start)
            self.prefill_tokens += nxt - start
            self.flops_outside_experts += cost.forward_flops(
                config, nxt - start, start,
                logit_rows=0 if first is None else 1)
            return nxt, first

        engine.decode_step, engine.prefill_chunk = decode_step, prefill_chunk

    def request(self, rid: int, prompt: np.ndarray, max_new: int):
        return self.Request(rid=rid, prompt=prompt, max_new_tokens=max_new)

    def facts(self) -> Dict[str, Any]:
        """What the rooflines and the counter readers need of the model: the
        configuration's sizes (``moe_topk``: the experts a token chooses,
        under the name ``moe_held_assignments_per_token`` reads) and the bytes
        of slot state the engine holds (under the name ``ssm_state_gb``
        reads: the engine's ``stats()["ssm"]``)."""
        return {"layers": self.config["num_hidden_layers"],
                "heads": self.config["num_attention_heads"],
                "slots": self.traffic["engine"]["slots"],
                "ssm_state_bytes":
                    self.engine.stats()["ssm"]["state_bytes"],
                "model": {**{k: v for k, v in self.config.items()
                             if isinstance(v, (int, float, dict, list))
                             and k != "assumed"},
                          "moe_topk": self.config["num_experts_per_tok"]}}

    def hlo_texts(self) -> Dict[str, str]:
        """The decode program and one prefill program per bucket."""
        return {label: self.engine.executable_text(label)
                for label in self.engine.store_outcomes}

    def counters(self) -> Dict[str, Any]:
        """Running totals. The routing and slot-state counters live on the
        device and are read here (``engine.stats()``), before and after a
        window, never inside a step. ``required_flops``: every product
        outside the routed experts, and a held expert's for each token routed
        to it."""
        stats = self.engine.stats()
        moe, ssm = stats["moe"], stats["ssm"]
        out = {"decode_keys": self.decode_keys,
               "prefill_tokens": self.prefill_tokens,
               "required_flops": self.flops_outside_experts
               + moe["assignments_held"] * cost.expert_flops(self.config),
               "moe_assignments_held": moe["assignments_held"],
               "moe_assignments_zero": moe["assignments_zero"],
               "moe_assignments_absent": moe["assignments_absent"],
               "moe_experts_active": moe["experts_active"],
               "moe_decode_experts_active": moe["decode"]["experts_active"],
               "ssm_resets": ssm["resets"],
               "ssm_chunks_carried": ssm["chunks_carried"],
               "ssm_decode_rows": ssm["decode_rows"]}
        for j, rows in enumerate(moe["rows_per_expert"]):
            out[f"moe_expert_rows.{j}"] = rows
        return out

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
    with jax.default_device(device):
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
        hidden = [[ref.embed(dims, top["embed"], seq) for seq in seqs]
                  for _ in passes]
        layer_of = {(o.name, kind): jax.jit(
            functools.partial(ref.layer, o, dims, kind))
            for o in passes for kind in (KDA, ATTENTION)}
        for l in range(len(dims.layer_types)):
            kind, mixer_p, expert_p = layer_weights(config, key, l)
            hidden = [[layer_of[o.name, kind](h, mixer_p, expert_p)
                       for h in hs] for o, hs in zip(passes, hidden)]
            jax.block_until_ready(hidden)
            del mixer_p, expert_p
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
