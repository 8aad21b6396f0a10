"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One command, no arguments: drives the framework's two normal paths once,
through the entry points a user calls, on every local TPU chip and at the
full width of the flagship LM (vocab 32768, d_model 1024, 16 heads x 64,
16 layers, d_ff 4096, S = 2048, bf16; weights random from a seed):

- start:  ``hvd.init()`` over all local chips, device and mesh report;
- eager:  ``hvd.allreduce`` + ``allreduce_async(...).wait()`` vs NumPy;
- resnet: ResNet-50 (folded BN, 256/chip, bf16) through
          ``hvd.DistributedOptimizer`` + ``trainer.jit_step``;
- lm:     the flagship LM (8/chip) through
          ``trainer.make_transformer_train_step`` + ``trainer.train_loop``,
          with the Pallas flash forward AND backward kernels in the
          compiled step;
- serve:  the same config through ``ServeEngine`` + ``ServeScheduler``,
          with the compiled paged-decode kernel in the decode step, a cold
          then a warm boot from one artifact store, and — on more than one
          chip — the same on a chip other than the first.

A leg that raises fails the run. Exit 0 only when every leg passed on a
TPU; off-TPU the script exits 1 before any leg and prints no result. The
last two stdout lines are JSON: first the summary (per leg ``ok``, wall
seconds split into compile and run, peak memory per device, ending
``"claim": null`` — also written to ``chiprun_out/chip_smoke/summary.json``),
then, last, the verdict the driver reads, these keys and no others::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

One process owns the chips for the whole run. The numbers in the summary
are bring-up facts (did it compile, how long, how much memory), not
benchmark results.

``tests/test_chip_smoke.py`` runs the same leg functions at a toy width on
the virtual CPU mesh with the kernels in interpret mode.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the legs run at. ``FLAGSHIP`` is what the chip runs;
    the test passes a toy."""
    lm: Dict[str, Any]                  # TransformerConfig fields
    lm_batch_per_chip: int
    lm_seq: int
    lm_steps: int
    resnet: str                         # horovod_tpu.models constructor
    resnet_kwargs: Dict[str, Any]
    image_size: int
    resnet_batch_per_chip: int
    resnet_steps: int
    serve: Dict[str, Any]               # ServeEngine geometry ({} = defaults)
    n_requests: int
    prompt_len: int
    new_tokens: int


FLAGSHIP = Sizes(
    lm=dict(vocab_size=32768, d_model=1024, n_heads=16, head_dim=64,
            n_layers=16, d_ff=4096, max_seq=2048, scan_unroll=16,
            remat=False, mlp_recompute=True),
    lm_batch_per_chip=8, lm_seq=2048, lm_steps=4,
    resnet="ResNet50", resnet_kwargs=dict(folded_bn=True),
    image_size=224, resnet_batch_per_chip=256, resnet_steps=6,
    serve={},                 # engine defaults: 8 slots, page 128, 2048 ctx
    n_requests=6, prompt_len=300, new_tokens=32,
)


class Timer:
    """Wall seconds of a leg, split into compile and run."""

    def __init__(self):
        self.compile_s = 0.0
        self._t0 = time.perf_counter()

    def compiled(self, fn):
        t = time.perf_counter()
        out = fn()
        self.compile_s += time.perf_counter() - t
        return out

    def report(self) -> Dict[str, float]:
        wall = time.perf_counter() - self._t0
        return {"wall_s": round(wall, 2),
                "compile_s": round(self.compile_s, 2),
                "run_s": round(wall - self.compile_s, 2)}


def memory_report() -> List[Dict[str, int]]:
    """Per device: bytes in use now and the peak since process start
    (``memory_stats()`` is None on backends that do not report)."""
    import jax
    out = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out.append({"id": int(d.id),
                    "bytes_in_use": int(stats.get("bytes_in_use", 0)),
                    "peak_bytes_in_use": int(
                        stats.get("peak_bytes_in_use", 0))})
    return out


def _covers_all_devices(tree: Any) -> None:
    import jax
    want = set(jax.devices())
    for leaf in jax.tree.leaves(tree):
        got = set(leaf.sharding.device_set)
        assert got == want, (
            f"array of shape {leaf.shape} lives on {sorted(d.id for d in got)}"
            f", not on all {len(want)} devices")


# ---------------------------------------------------------------------------
# legs
# ---------------------------------------------------------------------------

def leg_start() -> Dict[str, Any]:
    import jax

    import horovod_tpu as hvd
    from horovod_tpu import native

    t = Timer()
    hvd.init()
    mesh = hvd.mesh()
    dev = jax.devices()[0]
    assert hvd.size() == len(jax.devices()), (hvd.size(), jax.devices())
    order = [int(d.id) for d in mesh.devices.reshape(-1)]
    info = {
        "ok": True, "jax": jax.__version__,
        "platform": dev.platform, "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "mesh": {k: int(v) for k, v in mesh.shape.items()},
        "mesh_device_order": order,
        "coords": {int(d.id): list(getattr(d, "coords", ()) or ())
                   for d in jax.devices()},
        "native_core": native.status(),
        "compile_cache": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
    }
    info.update(t.report())
    return info


def leg_eager() -> Dict[str, Any]:
    import jax.numpy as jnp

    import horovod_tpu as hvd

    t = Timer()
    n = hvd.size()
    x = (np.arange(n * 1024, dtype=np.float32).reshape(n, 1024) % 97) - 48.0
    got = hvd.allreduce(jnp.asarray(x), op=hvd.Sum)
    np.testing.assert_allclose(np.asarray(got), x.sum(0), rtol=1e-6)
    got = hvd.allreduce_async(jnp.asarray(x), op=hvd.Average,
                              name="chip_smoke.eager").wait()
    np.testing.assert_allclose(np.asarray(got), x.mean(0), rtol=1e-6)
    return dict(t.report(), ok=True, ranks=n)


def leg_resnet(sizes: Sizes) -> Dict[str, Any]:
    """The step ``bench.py`` measures: ``hvd.DistributedOptimizer`` inside
    a ``trainer.jit_step`` program, replicated params, batch over ``hvd``."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu import models
    from horovod_tpu.parallel.trainer import jit_step

    t = Timer()
    mesh = hvd.mesh()
    n = hvd.size()
    model = getattr(models, sizes.resnet)(
        num_classes=1000, dtype=jnp.bfloat16, **sizes.resnet_kwargs)
    optimizer = hvd.DistributedOptimizer(
        optax.sgd(0.01, momentum=0.9), op=hvd.Average)

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

    size = sizes.image_size
    # jitted: one cacheable compile instead of an eager op-by-op init
    variables = t.compiled(lambda: jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, size, size, 3), jnp.bfloat16)))
    repl = NamedSharding(mesh, P())
    data = NamedSharding(mesh, P("hvd"))
    params = jax.device_put(variables["params"], repl)
    state = (params, jax.device_put(variables["batch_stats"], repl),
             optimizer.init(params))
    rng = np.random.RandomState(0)
    batch = sizes.resnet_batch_per_chip * n
    x = jax.device_put(
        jnp.asarray(rng.rand(batch, size, size, 3), jnp.bfloat16), data)
    y = jax.device_put(
        jnp.asarray(rng.randint(0, 1000, (batch,)), jnp.int32), data)
    _covers_all_devices((params, x, y))

    # first call = compile + one step; the same batch every step, so a
    # working optimizer must bring the loss down
    state, loss = t.compiled(lambda: step(state, x, y))
    losses = [float(loss)]
    for _ in range(sizes.resnet_steps - 1):
        state, loss = step(state, x, y)
        losses.append(float(loss))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], f"loss did not decrease: {losses}"
    return dict(t.report(), ok=True, model=sizes.resnet,
                batch_per_chip=sizes.resnet_batch_per_chip,
                losses=[round(v, 4) for v in losses],
                memory=memory_report())


def _flash_vs_jnp(cfg, seq: int) -> float:
    """Largest error, relative to the largest reference value, of the
    dispatched attention (the flash kernels wherever ``enabled()`` says
    so) against the jnp path on one sequence: output and q/k/v grads."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.config import knobs
    from horovod_tpu.parallel.sequence import local_attention

    shape = (1, seq, cfg.n_heads, cfg.head_dim)
    q, k, v = (jax.random.normal(key, shape, jnp.float32).astype(cfg.dtype)
               for key in jax.random.split(jax.random.PRNGKey(2), 3))

    def out_and_grads(q, k, v):
        def f(q, k, v):
            o = local_attention(q, k, v, causal=True)
            return jnp.sum(o.astype(jnp.float32) ** 2), o
        (_, o), grads = jax.value_and_grad(f, (0, 1, 2), has_aux=True)(
            q, k, v)
        return (o,) + grads

    got = jax.jit(out_and_grads)(q, k, v)
    knobs.set_override("HOROVOD_TPU_PALLAS", "0")       # the jnp path
    try:
        with jax.default_matmul_precision("highest"):
            # a new function object: jit would otherwise reuse the trace
            # taken above, with the kernels in it
            want = jax.jit(lambda q, k, v: out_and_grads(q, k, v))(q, k, v)
    finally:
        knobs.clear_override("HOROVOD_TPU_PALLAS")
    return max(
        float(jnp.max(jnp.abs(g.astype(jnp.float32) - w.astype(jnp.float32)))
              / jnp.max(jnp.abs(w.astype(jnp.float32))))
        for g, w in zip(got, want))


def leg_lm(sizes: Sizes, on_chip: bool) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.analysis.rules_ir import (
        hlo_collectives, replica_group_size,
    )
    from horovod_tpu.models import TransformerConfig
    from horovod_tpu.ops.pallas.flash_attention import compiled_kernels
    from horovod_tpu.parallel import trainer

    t = Timer()
    mesh = hvd.mesh()
    n = hvd.size()
    cfg = TransformerConfig(dtype=jnp.bfloat16, dp_axis="hvd", **sizes.lm)
    init_fn, train_step = trainer.make_transformer_train_step(
        cfg, optax.sgd(0.01, momentum=0.9), mesh)
    state = t.compiled(lambda: init_fn(jax.random.PRNGKey(0)))
    rng = np.random.RandomState(0)
    shape = (sizes.lm_batch_per_chip * n, sizes.lm_seq)
    data = NamedSharding(mesh, P("hvd"))
    tokens = jax.device_put(
        jnp.asarray(rng.randint(0, cfg.vocab_size, shape), jnp.int32), data)
    labels = jax.device_put(
        jnp.asarray(rng.randint(0, cfg.vocab_size, shape), jnp.int32), data)
    _covers_all_devices((state.params, tokens, labels))

    # One AOT compile: the executable inspected here for its kernels and
    # collectives is the one train_loop dispatches below.
    compiled = t.compiled(
        lambda: train_step.lower(state, tokens, labels).compile())
    hlo = compiled.as_text()
    kernels = compiled_kernels(hlo)
    if on_chip:
        missing = {"hvd_flash_fwd", "hvd_flash_bwd_dq",
                   "hvd_flash_bwd_dkv"} - set(kernels)
        assert not missing, (
            f"compiled LM step lacks Mosaic flash kernels {sorted(missing)} "
            f"(found {kernels}): attention took the jnp path")
    group_sizes = sorted({replica_group_size(e["replica_groups"])
                          for e in hlo_collectives(hlo)
                          if e["kind"] == "all-reduce"
                          and e["replica_groups"]})
    if n > 1:
        assert n in group_sizes, (
            f"no all-reduce over all {n} chips in the compiled LM step "
            f"(group sizes {group_sizes})")

    attention_err = t.compiled(lambda: _flash_vs_jnp(cfg, sizes.lm_seq))
    assert attention_err < 2e-2, (
        f"flash attention differs from the jnp path by {attention_err} "
        f"of the largest value")

    losses: List[float] = []
    state, info = trainer.train_loop(
        compiled, state,
        ((tokens, labels) for _ in range(sizes.lm_steps)),
        on_step=lambda step, st, loss: losses.append(float(loss)))
    assert info["status"] == "completed", info
    assert info["final_step"] == sizes.lm_steps, info
    assert len(losses) == sizes.lm_steps and all(np.isfinite(losses)), losses
    mem = memory_report()
    if on_chip:
        idle = [m["id"] for m in mem if m["bytes_in_use"] <= 0]
        assert not idle, f"devices {idle} hold no state: {mem}"
    return dict(t.report(), ok=True, flash_kernels=kernels,
                attention_vs_jnp_rel_err=round(attention_err, 6),
                allreduce_group_sizes=group_sizes,
                batch_per_chip=sizes.lm_batch_per_chip, seq=sizes.lm_seq,
                losses=[round(v, 4) for v in losses], memory=mem)


def _requests(sizes: Sizes, vocab: int):
    from horovod_tpu.serving import Request
    rng = np.random.default_rng(0)
    return [Request(rid=i,
                    prompt=rng.integers(
                        0, vocab, sizes.prompt_len + 17 * i).astype(np.int32),
                    max_new_tokens=sizes.new_tokens)
            for i in range(sizes.n_requests)]


def _serve_once(cfg, params, mesh, sizes: Sizes, *, warm: bool,
                on_chip: bool) -> Dict[str, Any]:
    """Boot one engine, serve the request set, check what came out."""
    from horovod_tpu.ops.pallas.flash_attention import compiled_kernels
    from horovod_tpu.serving import ServeEngine, ServeScheduler

    t0 = time.perf_counter()
    engine = ServeEngine(cfg, params, mesh, **sizes.serve)
    boot_s = time.perf_counter() - t0
    st = engine.stats()
    outcomes = set(st["store_outcomes"].values())
    if not engine.reload_keeps_layout:
        # A reloaded program would hand the KV pool back re-laid
        # (docs/serving.md, "Pool layout"): every boot compiles in
        # process, and the second boot is held to the first's tokens only.
        assert st["builds"] > 0 and outcomes == {"unsupported"}, st
    elif warm:
        assert st["builds"] == 0 and outcomes == {"hit"}, (
            f"warm boot compiled: {st['builds']} builds, "
            f"{st['store_outcomes']}")
    else:
        assert st["builds"] > 0 and outcomes == {"miss"}, st
    kernels = compiled_kernels(engine.executable_text("serve_decode"))
    if on_chip:
        assert kernels.get("hvd_paged_decode"), (
            f"decode executable lacks the compiled paged-decode kernel "
            f"(found {kernels}): decode attention took the jnp path")

    done = ServeScheduler(engine).run(_requests(sizes, cfg.vocab_size))
    assert len(done) == sizes.n_requests, len(done)
    for r in done:
        assert r.error is None, r.error
        assert len(r.tokens) == sizes.new_tokens, (r.rid, len(r.tokens))
        assert all(0 <= tok < cfg.vocab_size for tok in r.tokens), r.rid
    rejected = engine.stats()["store_rejected"]
    assert not rejected, (
        f"{rejected} rejected their inputs and fell back to the jit path")
    devices = sorted(int(d.id) for d in engine.k_pages.sharding.device_set)
    return {"engine": engine, "boot_s": round(boot_s, 2),
            "builds": st["builds"], "kernels": kernels, "devices": devices,
            "tokens": {r.rid: list(r.tokens) for r in done}}


def _check_against_reference(engine, cfg, sizes: Sizes) -> Dict[str, Any]:
    """Two reference checks on a live engine. (1) A request decoded alone
    gives bitwise the tokens it got inside the batch. (2) On the engine's
    real page pool and block tables, mid-flight, the dispatched decode
    attention agrees with the jnp paged reference."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.serving import ServeScheduler, kv_cache as kvc

    solo = _requests(sizes, cfg.vocab_size)[0]
    ServeScheduler(engine).run([solo])

    prompt = _requests(sizes, cfg.vocab_size)[1].prompt
    slot = engine.reserve(int(prompt.size) + 8, prompt=prompt)
    assert slot is not None
    tok = engine.prefill(slot, prompt)
    toks = np.zeros((engine.slots,), np.int32)
    for _ in range(4):
        toks[slot] = tok
        tok = int(engine.decode_step(toks)[slot])
    bt, lengths = engine.tables.device_views()
    q = jax.random.normal(jax.random.PRNGKey(1),
                          (engine.slots, cfg.n_heads, cfg.head_dim),
                          jnp.float32).astype(cfg.dtype)
    scale = cfg.head_dim ** -0.5
    k_pages, v_pages = engine.k_pages[0], engine.v_pages[0]
    got = kvc.paged_decode_attention(q, k_pages, v_pages, bt, lengths, scale)
    with jax.default_matmul_precision("highest"):
        want = kvc.paged_attention_reference(q, k_pages, v_pages, bt,
                                             lengths, scale)
    err = float(jnp.max(jnp.abs(got - want)))
    assert np.isfinite(err) and err < 2e-3, (
        f"paged decode attention differs from the jnp reference by {err}")
    assert float(jnp.max(jnp.abs(want[slot]))) > 0, "reference is all zero"
    engine.release(slot)
    return {"solo_tokens": list(solo.tokens), "attention_max_abs_err": err,
            "cached_tokens_checked": int(np.asarray(lengths)[slot])}


def leg_serve(sizes: Sizes, on_chip: bool, store_dir: str) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    import horovod_tpu as hvd
    from horovod_tpu.config import knobs
    from horovod_tpu.models import transformer as tfm

    t = Timer()
    lm = {k: v for k, v in sizes.lm.items() if k != "scan_unroll"}
    cfg = tfm.TransformerConfig(dtype=jnp.bfloat16, dp_axis=None, **lm)
    params = jax.jit(lambda r: tfm.init_params(cfg, r))(
        jax.random.PRNGKey(0))
    # A cold first boot needs an empty store; the path is fixed so that
    # nothing about the run depends on a temporary name.
    shutil.rmtree(store_dir, ignore_errors=True)
    knobs.set_override("HOROVOD_ARTIFACT_STORE", store_dir)
    try:
        placements = [("default", None)]
        if hvd.size() > 1:
            # a one-chip replica on a chip that is NOT jax.devices()[0]
            placements.append(("last_chip", Mesh(
                np.array([jax.devices()[-1]]), ("replica",))))
        out: Dict[str, Any] = {}
        reference = None
        for name, mesh in placements:
            cold = _serve_once(cfg, params, mesh, sizes, warm=False,
                               on_chip=on_chip)
            t.compile_s += cold["boot_s"]
            del cold["engine"]
            warm = _serve_once(cfg, params, mesh, sizes, warm=True,
                               on_chip=on_chip)
            assert warm["tokens"] == cold["tokens"], (
                f"{name}: the store-loaded engine decoded other tokens "
                f"than the engine that compiled them")
            assert warm["devices"] == cold["devices"], (warm["devices"],
                                                        cold["devices"])
            if name == "last_chip":
                assert cold["devices"] == [int(jax.devices()[-1].id)], cold
            if reference is None:
                reference = warm["tokens"]
                check = _check_against_reference(warm["engine"], cfg, sizes)
                assert check["solo_tokens"] == reference[0], (
                    "request 0 decoded alone differs from its batched "
                    "tokens", check["solo_tokens"], reference[0])
                out["reference_check"] = {
                    k: v for k, v in check.items() if k != "solo_tokens"}
            del warm["engine"]
            out[name] = {
                "devices": cold["devices"], "cold_builds": cold["builds"],
                "cold_boot_s": cold["boot_s"], "warm_builds": warm["builds"],
                "warm_boot_s": warm["boot_s"], "kernels": cold["kernels"]}
    finally:
        knobs.clear_override("HOROVOD_ARTIFACT_STORE")
        shutil.rmtree(store_dir, ignore_errors=True)
    return dict(t.report(), ok=True, requests=sizes.n_requests,
                new_tokens=sizes.new_tokens, memory=memory_report(), **out)


def run_legs(sizes: Sizes, on_chip: bool, store_dir: str) -> Dict[str, Any]:
    """Every leg in order, in this process. A leg that raises ends the
    run; nothing is caught."""
    import horovod_tpu as hvd

    legs: Dict[str, Any] = {}
    legs["start"] = leg_start()
    print(f"chip_smoke: start {json.dumps(legs['start'])}", flush=True)
    for name, leg in (
            ("eager", leg_eager),
            ("resnet", lambda: leg_resnet(sizes)),
            ("lm", lambda: leg_lm(sizes, on_chip)),
            ("serve", lambda: leg_serve(sizes, on_chip, store_dir))):
        legs[name] = leg()
        gc.collect()            # train state must be gone before serving
        print(f"chip_smoke: {name} ok {json.dumps(legs[name])}", flush=True)
    hvd.shutdown()
    return legs


def verdict(ok: bool) -> Dict[str, Any]:
    """The last stdout line: exactly ``ok`` and ``device``, the device as
    JAX reports it. Everything else belongs in the summary line."""
    import jax
    dev = jax.devices()[0]
    return {"ok": bool(ok),
            "device": {"platform": str(dev.platform),
                       "kind": str(dev.device_kind),
                       "count": len(jax.devices())}}


def main() -> int:
    from horovod_tpu.utils import compile_cache
    compile_cache.place(HERE)
    import jax

    t0 = time.perf_counter()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU — JAX found {dev.platform!r} "
              f"({dev.device_kind}). This script proves the system on the "
              f"chip; the CPU run of its legs is tests/test_chip_smoke.py.",
              file=sys.stderr)
        return 1
    legs = run_legs(FLAGSHIP, on_chip=True,
                    store_dir=os.path.join(OUT_DIR, "store"))
    last = verdict(all(leg["ok"] for leg in legs.values()))
    summary = dict(last, jax=jax.__version__,
                   wall_s=round(time.perf_counter() - t0, 2),
                   legs=legs, claim=None)
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(f"chip_smoke: summary {json.dumps(summary)}", flush=True)
    print(json.dumps(last), flush=True)
    return 0 if last["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
