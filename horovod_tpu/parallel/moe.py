"""Expert parallelism (MoE) over a mesh axis — alltoall dispatch/combine.

The reference exposes only the EP *substrate*: variable-split alltoall
(EnqueueTensorAlltoall operations.cc:1881, NCCLAlltoall nccl_operations.cc:1156
grouped P2P) plus process sets for expert groups (SURVEY §2.4 "EP substrate").
This module is the full scheme: a top-1 (switch) router with capacity-bounded
static-shape dispatch, ``lax.all_to_all`` token exchange across the ``ep``
axis, expert FFN on local experts, and the inverse combine — the MoE-style
expert dispatch named in BASELINE.json config 5.

TPU-native choices: everything is static-shape (capacity buffers instead of
the reference's dynamic recv-splits — dynamic shapes would force recompiles),
dispatch/combine are one-hot matmuls (MXU-friendly, the standard TPU MoE
formulation), and the exchange is a single XLA AllToAll on ICI.

Beside the switch layer (:func:`moe_ffn`) stands the layer a chip runs when
it holds a SHARE of a top-k expert block (:func:`topk_route`,
:func:`expert_share_ffn`): the router keeps its full width and its experts
per token, the chip is told which routed experts it holds (``first``,
``count``), computes their terms and every zero-compute (identity) term of
its own tokens, and leaves out the absent experts' terms. No token is
dropped: an expert's rows are bounded by the tokens present, not by a
capacity factor. On one chip there is no exchange. Three gate rules give the
same :class:`TopKRouting` from one product and one top-k (``_route_scores``,
``_choose``): :func:`topk_route` (softmax over every router output, the
chosen not renormalised), :func:`topk_softmax_route` (top-k of the logits,
softmax over the chosen) and :func:`topk_sigmoid_route` (a sigmoid an
output, the chosen renormalised to sum to the scaling). The counters of a served share
(:func:`share_counter_state`, :func:`add_share_counts`,
:func:`share_routing_stats`) are what a model's serve bodies keep on the
device and its ``ServeModel.stats`` reads back.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


class MoEMetrics(NamedTuple):
    aux_loss: jax.Array       # load-balancing loss (switch-transformer style)
    dropped_fraction: jax.Array


def _top1_dispatch(gates: jax.Array, capacity: int):
    """Build capacity-bounded one-hot dispatch/combine tensors.

    gates: [T, E] router probabilities. Returns (dispatch [T, E, C] bool-ish,
    combine [T, E, C] float) where position (t, e, c) means token t occupies
    slot c of expert e.
    """
    t_count, n_exp = gates.shape
    expert = jnp.argmax(gates, axis=-1)                       # [T]
    onehot = jax.nn.one_hot(expert, n_exp, dtype=jnp.float32)  # [T, E]
    # position of each token within its expert's queue (cumsum over tokens)
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0            # [T, E]
    kept = (pos < capacity) & (onehot > 0)
    pos_clamped = jnp.clip(pos, 0, capacity - 1).astype(jnp.int32)
    slot = jax.nn.one_hot(pos_clamped, capacity, dtype=jnp.float32)  # [T,E,C]
    dispatch = slot * kept[..., None]
    gate_val = jnp.sum(gates * onehot, axis=-1, keepdims=True)  # [T, 1]
    combine = dispatch * gate_val[..., None]
    dropped = 1.0 - jnp.sum(dispatch) / jnp.maximum(t_count, 1)
    return dispatch, combine, onehot, dropped


def moe_ffn(
    x: jax.Array,
    router_w: jax.Array,
    w_in: jax.Array,
    w_out: jax.Array,
    ep_axis: Optional[str] = None,
    capacity_factor: float = 1.25,
    activation=jax.nn.gelu,
) -> Tuple[jax.Array, MoEMetrics]:
    """Switch-style MoE FFN.

    Args:
      x: [B, S, D] local activations (any leading dims; flattened to tokens).
      router_w: [D, E_total] router weights (replicated across ``ep``).
      w_in: [E_local, D, F] local experts' up-projection.
      w_out: [E_local, F, D] local experts' down-projection.
      ep_axis: mesh axis experts are sharded over; None = all experts local.

    Inside shard_map with ``ep_axis`` bound: E_total = E_local * ep_size, and
    tokens are exchanged with one AllToAll each way.
    """
    orig_shape = x.shape
    d_model = x.shape[-1]
    tokens = x.reshape(-1, d_model)                       # [T, D]
    t_count = tokens.shape[0]
    e_local = w_in.shape[0]
    ep = lax.axis_size(ep_axis) if ep_axis else 1
    e_total = e_local * ep
    if router_w.shape[-1] != e_total:
        raise ValueError(
            f"router has {router_w.shape[-1]} experts, mesh provides "
            f"{e_total} ({e_local} local x ep={ep})")

    logits = tokens.astype(jnp.float32) @ router_w.astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)                # [T, E_total]
    capacity = max(1, int(capacity_factor * t_count / e_total))
    dispatch, combine, onehot, dropped = _top1_dispatch(gates, capacity)

    # Load-balancing aux loss (Switch Transformer eq. 4).
    frac_tokens = jnp.mean(onehot, axis=0)
    frac_gates = jnp.mean(gates, axis=0)
    aux = e_total * jnp.sum(frac_tokens * frac_gates)

    # [T, E, C] x [T, D] -> [E_total, C, D] expert input buffers
    expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), tokens)
    if ep_axis:
        # Exchange: chip j holds inputs for ALL experts from ITS tokens; after
        # the AllToAll chip k holds inputs for ITS e_local experts from all
        # chips' tokens, [E_local, ep * C, D].
        blocks = expert_in.reshape(ep, e_local, capacity, d_model)
        recv = lax.all_to_all(blocks, ep_axis, split_axis=0, concat_axis=0)
        # recv: [ep(source chip), e_local, C, D]
        expert_in = jnp.moveaxis(recv, 0, 1).reshape(
            e_local, ep * capacity, d_model)
    h = jnp.einsum("ecd,edf->ecf", expert_in, w_in.astype(expert_in.dtype))
    h = activation(h)
    expert_out = jnp.einsum("ecf,efd->ecd", h, w_out.astype(h.dtype))
    if ep_axis:
        # Inverse exchange: send each source chip its tokens' outputs back.
        back = jnp.moveaxis(
            expert_out.reshape(e_local, ep, capacity, d_model), 1, 0)
        recv = lax.all_to_all(back, ep_axis, split_axis=0, concat_axis=0)
        # recv: [ep(expert-owner chip), e_local, C, D] -> [E_total, C, D]
        expert_out = recv.reshape(e_total, capacity, d_model)
    out = jnp.einsum("tec,ecd->td", combine.astype(expert_out.dtype),
                     expert_out)
    return out.reshape(orig_shape), MoEMetrics(aux, dropped)


# ---------------------------------------------------------------------------
# top-k routing over a share of the experts (no capacity, no drop)
# ---------------------------------------------------------------------------

class TopKRouting(NamedTuple):
    experts: jax.Array        # [T, k] int32, ids over ALL router outputs
    gates: jax.Array          # [T, k] float32, as the gate rule weighs them


# Layout of the counters :func:`expert_share_ffn` returns, ``[4 + count]``:
# assignments to experts held here, to zero-compute experts, to absent
# experts, held experts that got at least one row, then rows per held expert.
N_SHARE_TOTALS = 4


def _route_scores(x: jax.Array, router_w: jax.Array) -> jax.Array:
    """``float32(x) Wr`` at ``highest``: on a TPU a float32 product
    otherwise runs in one bfloat16 pass, and a rounded score flips a
    choice."""
    return jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST)


def _choose(scores: jax.Array, k: int, bias: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """(ids ``[T, k]`` int32, their scores ``[T, k]``) of the top-k of
    ``scores + bias``: a bias (a learned correction) moves the choice,
    never the score that becomes the weight."""
    if bias is None:
        chosen, experts = lax.top_k(scores, k)
    else:
        _, experts = lax.top_k(scores + bias.astype(jnp.float32), k)
        chosen = jnp.take_along_axis(scores, experts, axis=-1)
    return experts.astype(jnp.int32), chosen


def topk_route(x: jax.Array, router_w: jax.Array, bias: jax.Array, k: int,
               scaling: float) -> TopKRouting:
    """``p = softmax(float32(x) Wr)`` over every router output; the ``k``
    chosen are the top-k of ``p + bias``; ``g_e = scaling * p_e``, not
    renormalised over the chosen."""
    p = jax.nn.softmax(_route_scores(x, router_w), axis=-1)
    experts, chosen = _choose(p, k, bias)
    return TopKRouting(experts, chosen * scaling)


def topk_softmax_route(x: jax.Array, router_w: jax.Array, k: int
                       ) -> TopKRouting:
    """``l = float32(x) Wr``; the ``k`` chosen are the top-k of the logits
    ``l``; ``g = softmax(l_chosen)`` over the chosen alone, so a token's
    gates sum to 1."""
    experts, chosen = _choose(_route_scores(x, router_w), k)
    return TopKRouting(experts, jax.nn.softmax(chosen, axis=-1))


def topk_sigmoid_route(x: jax.Array, router_w: jax.Array, bias: jax.Array,
                       k: int, scaling: float) -> TopKRouting:
    """``s = sigmoid(float32(x) Wr)``, each output on its own; the ``k``
    chosen are the top-k of ``s + bias``; ``g = scaling * s_chosen /
    sum(s_chosen)``: renormalised over the chosen, so a token's gates sum to
    ``scaling``."""
    s = jax.nn.sigmoid(_route_scores(x, router_w))
    experts, chosen = _choose(s, k, bias)
    return TopKRouting(
        experts, chosen / jnp.sum(chosen, axis=-1, keepdims=True) * scaling)


def share_gates(routing: TopKRouting, n_routed: int, first: int, count: int
                ) -> Tuple[jax.Array, jax.Array]:
    """(``[T, count]`` gate of each held expert, 0 where the token did not
    choose it; ``[T]`` summed gate of the token's zero-compute experts, ids
    ``>= n_routed``)."""
    local = routing.experts - first                               # [T, k]
    held = (local[..., None] == jnp.arange(count)[None, None, :])
    g_held = jnp.sum(jnp.where(held, routing.gates[..., None], 0.0), axis=1)
    g_zero = jnp.sum(jnp.where(routing.experts >= n_routed, routing.gates,
                               0.0), axis=-1)
    return g_held, g_zero


def share_counts(routing: TopKRouting, n_routed: int, first: int, count: int,
                 valid: Optional[jax.Array] = None) -> jax.Array:
    """The routing counters of one call, ``[N_SHARE_TOTALS + count]`` int32
    (layout above), over the rows ``valid`` marks (padding routes too, and
    is not counted)."""
    e = routing.experts
    rows = jnp.ones(e.shape[:1], bool) if valid is None else valid
    held = (e >= first) & (e < first + count) & rows[:, None]
    zero = (e >= n_routed) & rows[:, None]
    n_held, n_zero = jnp.sum(held), jnp.sum(zero)
    per_expert = jnp.sum(
        held[..., None] & ((e - first)[..., None]
                           == jnp.arange(count)[None, None, :]), axis=(0, 1))
    totals = jnp.stack([n_held, n_zero,
                        jnp.sum(rows) * e.shape[1] - n_held - n_zero,
                        jnp.sum(per_expert > 0)])
    return jnp.concatenate([totals, per_expert]).astype(jnp.int32)


def expert_share_ffn(x: jax.Array, routing: TopKRouting, w_gate: jax.Array,
                     w_up: jax.Array, w_down: jax.Array, *, n_routed: int,
                     first: int = 0) -> jax.Array:
    """This chip's part of a top-k SwiGLU expert block with zero-compute
    identity experts::

        s = sum_{chosen e held here} g_e SwiGLU_e(x) + sum_{chosen e >= n_routed} g_e x

    x ``[T, D]``; ``w_gate`` / ``w_up`` ``[count, D, F]`` and ``w_down``
    ``[count, F, D]`` are routed experts ``first .. first + count``. The
    terms of routed experts held elsewhere are left out; summed over all the
    shares, with the identity terms counted once, the parts give the whole
    layer. A masked product: every held expert runs over every row present
    and the gate (0 where the token chose another) folds into its
    activation, so the down projection is ONE product over ``count * F`` and
    no token can overflow a buffer. At a few rows an expert it streams the
    same weights a grouped product would; at a prefill chunk of T rows it
    does ``count * T`` row-products where about ``T * k * count / E`` are
    needed. Scopes: ``hvd_moe_experts`` (the products), ``hvd_moe_combine``
    (the gates of the held experts and the identity term).
    """
    count = w_gate.shape[0]
    with jax.named_scope("hvd_moe_combine"):
        g_held, g_zero = share_gates(routing, n_routed, first, count)
    with jax.named_scope("hvd_moe_experts"):
        hg = jnp.einsum("td,edf->tef", x, w_gate.astype(x.dtype))
        hu = jnp.einsum("td,edf->tef", x, w_up.astype(x.dtype))
        act = (jax.nn.silu(hg.astype(jnp.float32)) * hu.astype(jnp.float32)
               * g_held[..., None]).astype(x.dtype)
        s = jnp.einsum("tef,efd->td", act, w_down.astype(x.dtype),
                       preferred_element_type=jnp.float32)
    with jax.named_scope("hvd_moe_combine"):
        return s + g_zero[:, None] * x.astype(jnp.float32)


# ---------------------------------------------------------------------------
# a served share's routing counters, kept on the device
# ---------------------------------------------------------------------------

DECODE, PREFILL = 0, 1      # which program a routing counter counted in


def share_counter_state(held: int) -> jax.ShapeDtypeStruct:
    """The routing counters on the device: ``[2, 2, 4 + held]`` uint32 —
    the low words and the high, so a total only grows past 2**32; the decode
    program's and the prefill program's; :func:`share_counts`' layout."""
    return jax.ShapeDtypeStruct((2, 2, N_SHARE_TOTALS + held), jnp.uint32)


def add_share_counts(counters: jax.Array, added: jax.Array, program: int
                     ) -> jax.Array:
    """``counters`` with ``added`` (one call's counts, int32) on the totals
    of ``program``, the carry out of the low word taken into the high."""
    was = counters[0, program]
    low = was + added.astype(jnp.uint32)
    high = counters[1, program] + (low < was).astype(jnp.uint32)
    return counters.at[:, program].set(jnp.stack([low, high]))


def counter_totals(counters: Any):
    """The two words of a counter array ``[2, ...]`` read back as one
    ``uint64`` array ``[...]``."""
    import numpy as np
    words = np.asarray(counters).astype(np.uint64)
    return (words[1] << np.uint64(32)) + words[0]


def share_routing_stats(counters: jax.Array, first: int, held: int
                        ) -> Dict[str, Any]:
    """``engine.stats()["moe"]``: the counters read back (the one place),
    and published as ``hvd_serve_moe_*`` gauges."""
    from horovod_tpu import metrics as M
    by_program = counter_totals(counters)               # [2, 4 + held]
    n = N_SHARE_TOTALS

    def named(totals):
        totals = [int(v) for v in totals]
        return {"assignments_held": totals[0], "assignments_zero": totals[1],
                "assignments_absent": totals[2], "experts_active": totals[3],
                "rows_per_expert": totals[n:]}

    out = {**named(by_program.sum(axis=0)),
           "decode": named(by_program[DECODE]),
           "prefill": named(by_program[PREFILL]),
           "expert_first": first, "experts_held": held}
    for key, what in (
            ("assignments_held", "to routed experts held on this chip"),
            ("assignments_zero", "to zero-compute (identity) experts"),
            ("assignments_absent", "to routed experts held elsewhere")):
        M.gauge(f"hvd_serve_moe_{key}",
                f"Token-to-expert assignments {what}, all layers and "
                f"steps").set(out[key])
    M.gauge("hvd_serve_moe_experts_active",
            "Held experts that got at least one row, summed over layers "
            "and steps").set(out["experts_active"])
    rows = M.gauge("hvd_serve_moe_expert_rows",
                   "Rows routed to each held expert, all layers and steps",
                   labelnames=("expert",))
    for j, v in enumerate(out["rows_per_expert"]):
        rows.labels(expert=str(first + j)).set(v)
    return {"moe": out}
