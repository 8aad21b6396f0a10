"""One chip's share of the DeepSeek-V3 stack that Kimi K2 runs (latent
attention under a YaRN-stretched rotary in every layer, a leading dense
SwiGLU layer, sigmoid-routed experts plus a shared expert in the rest):
weights from the seed, the program's serving engine on them, and the plain
reference bound to the same weights. Only ``program_config`` and
``ServeProgram`` import the program.

Configuration keys follow the model's public ``config.json``. The file is
cut: ``n_routed_experts`` counts the routed experts held here (the first of
the published count; the router keeps its published width),
``num_hidden_layers`` and ``vocab_size`` what this chip holds.

Weights are bfloat16 on the device, as the checkpoint is published: drawn
leaf by leaf in float32 and cast inside one compiled call, so no float32
stack outlives its leaf. A stacked leaf's layers have a key each, so the
reference draws one layer at a time (float32 weights of the whole cut are
14 GB, one expert layer's 2.7 GB)."""

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
from benchmarks.reference import kimi_k2 as ref
from benchmarks.roofline import kimi_k2 as cost

DENSE, MOE = ref.DENSE, ref.MOE
# the configuration file's ``assumed.weights``: the embedding's rows N(0, 1);
# a routed expert's down projection at a quarter of the shared expert's
# deviation (a flipped eighth choice, equal gates under this rule whatever
# the router's gain, is the program's largest own error, Solar's reading)
EMBED_DEVIATION = 1.0
ROUTED_DOWN_GAIN = 0.25


def held_experts(config: Dict[str, Any]) -> int:
    return config["n_routed_experts"]


def _gain(config: Dict[str, Any], name: str) -> float:
    """What a product's N(0, 1 / fan_in) draw is multiplied by: 1 /
    mscale(factor, mscale_all_dim)^2 on ``wq_b`` (the queries), what the
    softmax scale is multiplied by taken back out, so that a score at the
    YaRN scale has the deviation of about 1 a trained model's has;
    ``ROUTED_DOWN_GAIN`` on a routed expert's down projection; 1 else."""
    if name.endswith("['wq_b']"):
        dims = ref.dims_of(config)
        return (dims.nope + dims.rope) ** -0.5 / ref.softmax_scale(dims)
    if name.endswith("['moe']['w_down']"):
        return ROUTED_DOWN_GAIN
    return 1.0


def shapes(config: Dict[str, Any]) -> Dict[str, Any]:
    """(shape, fan-in) of every leaf of the parameter tree the program
    takes; fan-in None for a norm scale and the routing bias. The
    benchmark's own table, not the program's: the reference takes nothing
    the program made (a test holds the two trees to the same shapes)."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    rq, rkv = config["q_lora_rank"], config["kv_lora_rank"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    layers = cost.layer_counts(config)
    l, ld, lm = layers["all"], layers["dense"], layers["moe"]
    e, fe = held_experts(config), config["moe_intermediate_size"]
    f, fs = config["intermediate_size"], fe * config["n_shared_experts"]
    n = cost.router_width(config)
    attention = {"attn_norm": ((l, d), None),
                 "wq_a": ((l, d, rq), d), "q_norm": ((l, rq), None),
                 "wq_b": ((l, rq, h * (dn + dr)), rq),
                 "wkv_a": ((l, d, rkv + dr), d), "kv_norm": ((l, rkv), None),
                 "wkv_b": ((l, rkv, h * (dn + dv)), rkv),
                 "wo": ((l, h * dv, d), h * dv)}
    dense = {"norm": ((ld, d), None), "w_gate": ((ld, d, f), d),
             "w_up": ((ld, d, f), d), "w_down": ((ld, f, d), f)}
    moe = {"norm": ((lm, d), None), "router": ((lm, d, n), d),
           "router_bias": ((lm, n), None),
           "w_gate": ((lm, e, d, fe), d), "w_up": ((lm, e, d, fe), d),
           "w_down": ((lm, e, fe, d), fe),
           "shared": {"w_gate": ((lm, d, fs), d), "w_up": ((lm, d, fs), d),
                      "w_down": ((lm, fs, d), fs)}}
    v = config["vocab_size"]
    return {"embed": ((v, d), d), "final_norm": ((d,), None),
            "head": ((d, v), d),
            "layers": {"mla": attention, DENSE: dense, MOE: moe}}


def _is_leaf(x: Any) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def _kind(name: str, fan_in: Optional[int]) -> str:
    """How a leaf is drawn (``_draw``)."""
    if name.endswith("['router_bias']"):
        return "bias"
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
        if fan_in is None:
            scale = None
        elif name == "['embed']":
            scale = EMBED_DEVIATION
        else:
            scale = _gain(config, name) * fan_in ** -0.5
        out.append((name, shape, _kind(name, fan_in), scale))
    return out, treedef


BLOCK = (1024, 8192)        # numbers one compiled draw makes at a time


def _draw(kind: str, scale: Optional[float], shape: Tuple[int, ...],
          key: jax.Array) -> jax.Array:
    """One leaf, whole: a product ~ N(0, scale^2) (``scale`` = gain /
    sqrt(fan_in); the embedding's rows N(0, 1)) rounded to bfloat16, the
    router rounded likewise and kept in float32 (it is served in float32),
    a norm scale 1 +- 0.1 in float32, the routing bias zero. Drawn in
    blocks of ``BLOCK`` numbers, each from its own key, one after another
    (``lax.map``): the TPU compiler takes ~1 s over that whatever the
    leaf's shape, where one normal of a stacked leaf's shape took it 8-11
    s (compile-only for a v5e, PR 39), and a run from an empty compile
    cache paid that 21 times."""
    if kind == "bias":
        return jnp.zeros(shape, jnp.float32)

    def block(k):
        z = jax.random.normal(k, BLOCK, jnp.float32)
        if kind == "scale":
            return 1.0 + 0.1 * z
        w = (z * scale).astype(jnp.bfloat16)
        return w.astype(jnp.float32) if kind == "router" else w

    n = math.prod(shape)
    per_block = BLOCK[0] * BLOCK[1]
    keys = jax.random.split(key, -(-n // per_block))
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
    """Layer ``layer`` of the stack, float32: (its kind, its attention
    block's weights, its dense SwiGLU's or expert half's) — the values
    ``weights`` puts at index ``layer`` of the attention blocks' stacked
    leaves and at the layer's index among its kind of the others'. Each
    stacked leaf is drawn whole again (the call ``weights`` compiled) and
    the layer taken out of it."""
    kinds = [ref.layer_kind(ref.dims_of(config), l)
             for l in range(config["num_hidden_layers"])]
    kind = kinds[layer]
    index = {"mla": layer, kind: kinds[:layer].count(kind)}
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
        for group in ("mla", kind)))


def program_config(config: Dict[str, Any], **kw):
    from horovod_tpu.models import KimiK2Config
    from horovod_tpu.models.transformer import RopeScaling
    dims = ref.dims_of(config)      # refuses the switches not written down
    factor, original, beta_fast, beta_slow, mscale, mscale_all = dims.yarn
    return KimiK2Config(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers_total=config["num_hidden_layers"],
        first_k_dense=config["first_k_dense_replace"],
        d_ff=config["intermediate_size"],
        n_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"], v_dim=config["v_head_dim"],
        n_routed_experts=cost.router_width(config),
        top_k=config["num_experts_per_tok"],
        routed_scaling=float(config["routed_scaling_factor"]),
        d_expert=config["moe_intermediate_size"],
        d_shared=config["moe_intermediate_size"]
        * config["n_shared_experts"],
        expert_first=0, expert_count=held_experts(config),
        rope_theta=float(config["rope_theta"]),
        rope_scaling=RopeScaling(
            factor=factor, original_max_position=original,
            beta_fast=beta_fast, beta_slow=beta_slow, mscale=mscale,
            mscale_all_dim=mscale_all),
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
        self.context = self.engine.n_max_pages * self.engine.page
        self.decode_s: List[float] = []         # host time of each decode step
        self.decode_keys: List[List[int]] = []  # cached keys per slot in use
        self.prefill_tokens = 0
        self.prefill_chunks = 0
        self.flops_outside_experts = 0.0
        self.prefill_flops_outside_experts = 0.0
        # latent rows the attention needed (live) and read (the gathered
        # block table: max_seq a sequence), per attention block
        self.latent_rows = {"decode_live": 0, "decode_read": 0,
                            "prefill_live": 0, "prefill_read": 0}
        self._wrap(spans)

    def _wrap(self, spans) -> None:
        engine, config = self.engine, self.config
        decode, prefill = engine.decode_step, engine.prefill_chunk
        rows = self.latent_rows

        def decode_step(tokens, active=None):
            lengths = engine.tables.lengths
            keys = [int(n) + 1 for n in (lengths[active] if active is not None
                                         else lengths[lengths > 0])]
            t0 = time.perf_counter()
            with spans.span("bench.decode"):
                out = decode(tokens, active=active)
            self.decode_s.append(time.perf_counter() - t0)
            self.decode_keys.append(keys)
            rows["decode_live"] += sum(keys)
            rows["decode_read"] += engine.slots * self.context
            self.flops_outside_experts += sum(
                cost.forward_flops(config, 1, n - 1) for n in keys)
            return out

        def prefill_chunk(slot, prompt, start):
            with spans.span("bench.prefill"):
                nxt, first = prefill(slot, prompt, start)
            self.prefill_tokens += nxt - start
            self.prefill_chunks += 1
            rows["prefill_live"] += nxt
            rows["prefill_read"] += self.context
            flops = cost.forward_flops(config, nxt - start, start,
                                       logit_rows=0 if first is None else 1)
            self.flops_outside_experts += flops
            self.prefill_flops_outside_experts += flops
            return nxt, first

        engine.decode_step, engine.prefill_chunk = decode_step, prefill_chunk

    def request(self, rid: int, prompt: np.ndarray, max_new: int):
        return self.Request(rid=rid, prompt=prompt, max_new_tokens=max_new)

    def facts(self) -> Dict[str, Any]:
        """What the rooflines and the counter readers need of the model: the
        configuration's sizes (``moe_topk``: the experts a token chooses,
        under the name ``moe_held_assignments_per_token`` reads)."""
        return {"layers": self.config["num_hidden_layers"],
                "heads": self.config["num_attention_heads"],
                "slots": self.traffic["engine"]["slots"],
                "model": {**{k: v for k, v in self.config.items()
                             if isinstance(v, (int, float, dict))
                             and k != "assumed"},
                          "moe_topk": self.config["num_experts_per_tok"]}}

    def hlo_texts(self) -> Dict[str, str]:
        """The decode program and one prefill program per bucket."""
        return {label: self.engine.executable_text(label)
                for label in self.engine.store_outcomes}

    def counters(self) -> Dict[str, Any]:
        """Running totals. The routing counters live on the device and are
        read here (``engine.stats()``), before and after a window, never
        inside a step. ``required_flops``: every product outside the routed
        experts, and a held expert's for each token routed to it;
        ``prefill_required_flops`` the same of the prefill chunks alone."""
        moe = self.engine.stats()["moe"]
        expert = cost.expert_flops(self.config)
        out = {"decode_keys": self.decode_keys,
               "prefill_tokens": self.prefill_tokens,
               "prefill_chunks": self.prefill_chunks,
               "required_flops": self.flops_outside_experts
               + moe["assignments_held"] * expert,
               "prefill_required_flops": self.prefill_flops_outside_experts
               + moe["prefill"]["assignments_held"] * expert,
               "moe_assignments_held": moe["assignments_held"],
               "moe_assignments_zero": moe["assignments_zero"],
               "moe_assignments_absent": moe["assignments_absent"],
               "moe_experts_active": moe["experts_active"],
               "moe_decode_experts_active": moe["decode"]["experts_active"]}
        for key, n in self.latent_rows.items():
            out[f"mla_rows_{key}"] = n
        for j, n in enumerate(moe["rows_per_expert"]):
            out[f"moe_expert_rows.{j}"] = n
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
        # the attention half is one program for every layer, the second
        # half one for each kind: three compiles a precision, not two
        # whole layers' (a cold run pays every one of them)
        attend_of = {o.name: jax.jit(functools.partial(
            ref.attention_half, o, dims)) for o in passes}
        ffn_of = {(o.name, kind): jax.jit(functools.partial(
            ref.ffn_half, o, dims, kind))
            for o in passes for kind in (DENSE, MOE)}
        for l in range(config["num_hidden_layers"]):
            kind, block_p, ffn_p = layer_weights(config, key, l)
            hidden = [[ffn_of[o.name, kind](attend_of[o.name](h, block_p),
                                            ffn_p)
                       for h in hs] for o, hs in zip(passes, hidden)]
            jax.block_until_ready(hidden)
            del block_p, ffn_p
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
