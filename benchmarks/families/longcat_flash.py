"""One chip's share of LongCat-Flash (two latent-attention blocks, two dense
SwiGLU FFNs and a top-k expert block on a shortcut a layer): weights from the
seed, the program's serving engine on them, and the plain reference bound to
the same weights. Only ``program_config`` and ``ServeProgram`` import the
program.

Configuration keys follow the model's public ``config.json``. The file is
cut: ``n_routed_experts`` counts the routed experts held here (the first of
the published count; the router keeps its published width), ``num_layers``
and ``vocab_size`` what this chip holds.

Weights are bfloat16 on the device, as the checkpoint is published: drawn
leaf by leaf in float32 and cast inside one compiled call, so no float32
stack outlives its leaf. A stacked leaf's layers have a key each, so the
reference draws one layer at a time (float32 weights of the whole cut are
20.7 GB and one layer's 5 GB)."""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import lowprec, trees
from benchmarks.reference import longcat_flash as ref
from benchmarks.roofline import longcat_flash as cost

ROUTER_GAIN = 5.0       # the configuration file's ``assumed.weights``


def held_experts(config: Dict[str, Any]) -> int:
    return config["n_routed_experts"]


def routed_experts(config: Dict[str, Any]) -> int:
    """The router's routed outputs: the published count where the file is
    cut to a share."""
    return config.get("published", {}).get("n_routed_experts",
                                           config["n_routed_experts"])


def shapes(config: Dict[str, Any]) -> Dict[str, Any]:
    """(shape, fan-in) of every leaf of the parameter tree the program
    takes; fan-in None for a norm scale and the routing bias. The
    benchmark's own table, not the program's: the reference takes nothing
    the program made (a test holds the two trees to the same shapes)."""
    d, l, h = (config["hidden_size"], config["num_layers"],
               config["num_attention_heads"])
    rq, rkv = config["q_lora_rank"], config["kv_lora_rank"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    f, fe = config["ffn_hidden_size"], config["expert_ffn_hidden_size"]
    e, width = held_experts(config), cost.router_width(config)
    mla = {"attn_norm": ((l, d), None),
           "wq_a": ((l, d, rq), d), "q_norm": ((l, rq), None),
           "wq_b": ((l, rq, h * (dn + dr)), rq),
           "wkv_a": ((l, d, rkv + dr), d), "kv_norm": ((l, rkv), None),
           "wkv_b": ((l, rkv, h * (dn + dv)), rkv),
           "wo": ((l, h * dv, d), h * dv)}
    ffn = {"ffn_norm": ((l, d), None), "w_gate": ((l, d, f), d),
           "w_up": ((l, d, f), d), "w_down": ((l, f, d), f)}
    moe = {"router": ((l, d, width), d), "router_bias": ((l, width), None),
           "w_gate": ((l, e, d, fe), d), "w_up": ((l, e, d, fe), d),
           "w_down": ((l, e, fe, d), fe)}
    v = config["vocab_size"]
    return {"embed": ((v, d), d), "final_norm": ((d,), None),
            "head": ((d, v), d),
            "layers": {"mla": (dict(mla), dict(mla)),
                       "ffn": (dict(ffn), dict(ffn)), "moe": moe}}


def _is_leaf(x: Any) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def _leaves(config: Dict[str, Any]
            ) -> Tuple[List[Tuple[str, Tuple, str, Optional[float]]], Any]:
    """(name, shape, kind, the deviation of its draw) of every leaf, in the
    tree's order, and the tree."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes(config), is_leaf=_is_leaf)
    out = []
    for path, (shape, fan_in) in flat:
        name = jax.tree_util.keystr(path)
        out.append((name, shape, _kind(name, fan_in),
                    None if fan_in is None
                    else _gain(config, name) * fan_in ** -0.5))
    return out, treedef


def _kind(name: str, fan_in: Optional[int]) -> str:
    if name.endswith("['router_bias']"):
        return "bias"
    if fan_in is None:
        return "scale"
    return "router" if name.endswith("['router']") else "product"


def _gain(config: Dict[str, Any], name: str) -> float:
    """What a product's N(0, 1 / fan_in) draw is multiplied by: the router's
    ROUTER_GAIN; and 1 / a_q on ``wq_b`` and 1 / a_kv on ``wkv_b``, whose inputs the model scales by a_q = sqrt(hidden /
    q_lora_rank) and a_kv = sqrt(hidden / kv_lora_rank), so that queries and
    keys come out at unit scale and a score at about 1, as a trained
    model's do; left at 1 the scores of random weights have a deviation of
    a_q a_kv ~ 6 and the softmax all but picks one key."""
    if name.endswith("['router']"):
        return ROUTER_GAIN
    if name.endswith("['wq_b']") and config["mla_scale_q_lora"]:
        return (config["q_lora_rank"] / config["hidden_size"]) ** 0.5
    if name.endswith("['wkv_b']") and config["mla_scale_kv_lora"]:
        return (config["kv_lora_rank"] / config["hidden_size"]) ** 0.5
    return 1.0


def _draw(kind: str, scale: Optional[float], shape: Tuple[int, ...],
          wide: bool, key: jax.Array) -> jax.Array:
    """One leaf, or one layer of a stacked leaf: a product ~ N(0, scale^2)
    (``scale`` = gain / sqrt(fan_in)) rounded to bfloat16, the router
    rounded likewise and kept in float32 (it is served in float32), a norm
    scale 1 +- 0.1 in float32, the routing bias zero. ``wide``: the
    bfloat16 values widened to float32, for the reference."""
    if kind == "bias":
        return jnp.zeros(shape, jnp.float32)
    if kind == "scale":
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    w = (jax.random.normal(key, shape, jnp.float32) * scale).astype(
        jnp.bfloat16)
    return w.astype(jnp.float32) if (wide or kind == "router") else w


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
    """Embedding, final norm and head, widened to float32."""
    leaves, _ = _leaves(config)
    return {name[2:-2]: _draw_one(kind, scale, shape, True,
                                  jax.random.fold_in(key, i))
            for i, (name, shape, kind, scale) in enumerate(leaves)
            if not name.startswith("['layers']")}


def layer_weights(config: Dict[str, Any], key: jax.Array, layer: int
                  ) -> Dict[str, Any]:
    """One layer's weights, widened to float32: the values ``weights`` puts
    at index ``layer`` of every stacked leaf."""
    leaves, _ = _leaves(config)
    drawn = [_draw_one(kind, scale, shape[1:], True,
                       _layer_keys(key, i, shape[0])[layer])
             for i, (name, shape, kind, scale) in enumerate(leaves)
             if name.startswith("['layers']")]
    treedef = jax.tree.structure(shapes(config)["layers"], is_leaf=_is_leaf)
    return jax.tree.unflatten(treedef, drawn)


def program_config(config: Dict[str, Any], **kw):
    from horovod_tpu.models import LongCatFlashConfig
    return LongCatFlashConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"], n_layers=config["num_layers"],
        d_ff=config["ffn_hidden_size"],
        d_expert=config["expert_ffn_hidden_size"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"], v_dim=config["v_head_dim"],
        mla_scale_q_lora=config["mla_scale_q_lora"],
        mla_scale_kv_lora=config["mla_scale_kv_lora"],
        n_routed_experts=routed_experts(config),
        n_zero_experts=config["zero_expert_num"], top_k=config["moe_topk"],
        routed_scaling=float(config["routed_scaling_factor"]),
        expert_first=0, expert_count=held_experts(config),
        rope_theta=float(config["rope_theta"]),
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
        """What the rooflines need of the model: the configuration's sizes."""
        return {"layers": self.config["num_layers"],
                "heads": self.config["num_attention_heads"],
                "slots": self.traffic["engine"]["slots"],
                "model": {k: v for k, v in self.config.items()
                          if isinstance(v, (int, float, dict))
                          and k != "assumed"}}

    def hlo_texts(self) -> Dict[str, str]:
        """The decode program and one prefill program per bucket."""
        return {label: self.engine.executable_text(label)
                for label in self.engine.store_outcomes}

    def counters(self) -> Dict[str, Any]:
        """Running totals. The routing counters live on the device and are
        read here (``engine.stats()``), before and after a window, never
        inside a step. ``required_flops``: every product outside the
        experts, and a held expert's for each token routed to it."""
        moe = self.engine.stats()["moe"]
        out = {"decode_keys": self.decode_keys,
               "prefill_tokens": self.prefill_tokens,
               "required_flops": self.flops_outside_experts
               + moe["assignments_held"] * cost.expert_flops(self.config),
               "moe_assignments_held": moe["assignments_held"],
               "moe_assignments_zero": moe["assignments_zero"],
               "moe_assignments_absent": moe["assignments_absent"],
               "moe_experts_active": moe["experts_active"],
               "moe_decode_experts_active": moe["decode"]["experts_active"]}
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
        hidden = [[top["embed"][seq] for seq in seqs] for _ in passes]
        layer_of = [jax.jit(functools.partial(ref.layer, o, dims))
                    for o in passes]
        for l in range(config["num_layers"]):
            lp = layer_weights(config, key, l)
            hidden = [[fn(h, lp) for h in hs]
                      for fn, hs in zip(layer_of, hidden)]
            jax.block_until_ready(hidden)
            del lp
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
