#!/usr/bin/env python3
"""Chip smoke test: serve llama3.2-1b at full published width on a TPU
through the fused ABFT kernels.

  python chip_smoke.py [--seed 0]     # one chip: kernels, serve, faults
  python chip_smoke.py --mesh 4       # four chips: sharded serving only

One process holds the chip and starts no child.  Phases (one chip):

  device   JAX must report a TPU; there is no CPU fallback (rehearse the
           serving path on CPU with ``python -m repro.launch.serve
           --scale smoke``).
  kernels  ``ops.abft_matmul`` ('1s') and the three flash wrappers run
           once at llama3.2-1b shapes and are compared with
           ``kernels/ref.py``; one injected accumulator fault must be
           flagged and located by the kernel.
  serve    a ``ServeEngine`` built as ``launch/serve.py`` builds it
           (intensity-guided policy, dense cache, bf16, random weights
           from ``--seed``) serves 8 requests in 8 slots, prompts of
           64-512 tokens, 32 new tokens each, ``max_len`` 1024.  Every
           request must finish with 0 detections and 0 errors, and the
           compiled decode step must hold the Pallas kernel
           (``tpu_custom_call``) at every site the plan runs as
           ``block_1s``.
  faults   the same requests again with the ``--inject-faults`` fault of
           ``launch/serve.py``: it must be detected and retried with 0
           hard faults, and the greedy streams must be byte-identical to
           the clean run.  First-step logits with protection and with
           ABFT off must agree within a bf16 tolerance.

``--mesh 4`` runs only the sharded path: the same requests through a
4-way tensor-parallel engine with the paged cache and through a 1-device
engine, whose greedy streams are compared.

Any failed check raises and exits non-zero.  The last line of standard
output is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
Token rates printed here are smoke figures, not metrics.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "llama3.2-1b"
SLOTS = 8
MAX_LEN = 1024
NEW_TOKENS = 32
PROMPT_LENS = (64, 512)
BF16_TOL = 2e-2          # rtol, and atol relative to the reference's scale


def log(phase: str, **kw) -> None:
    print(json.dumps({"phase": phase, **kw}, default=str), flush=True)


def check(ok, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def close_bf16(a, b) -> tuple[bool, float, float]:
    """(within bf16 tolerance, max |a - b|, max |b|)."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    scale = float(np.max(np.abs(b)))
    err = float(np.max(np.abs(a - b)))
    ok = bool(np.allclose(a, b, rtol=BF16_TOL, atol=BF16_TOL * scale))
    return ok, err, scale


# ------------------------------------------------------------------ kernels
def phase_kernels(cfg, seed: int) -> None:
    from repro.core.faults import FaultSpec
    from repro.kernels import flash_ops, ops
    from repro.kernels.ref import attention_ref, matmul_ref

    ks = jax.random.split(jax.random.PRNGKey(seed), 12)
    bf16 = jnp.bfloat16
    D, F = cfg.d_model, cfg.d_ff
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim

    def normal(k, shape, scale=1.0):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(
            bf16)

    x = normal(ks[0], (SLOTS, D))
    w = normal(ks[1], (D, F), D ** -0.5)
    y, chk = ops.abft_matmul(x, w, mode="1s")
    ok, err, scale = close_bf16(y, matmul_ref(x, w))
    log("kernels", kernel="abft_matmul[1s]", shape=[SLOTS, D, F],
        max_err=err, ref_scale=scale, flag=bool(chk.flag))
    check(ok, "abft_matmul agrees with matmul_ref")
    check(not bool(chk.flag), "clean abft_matmul does not flag")

    row, col = 3, 1000
    _, chk = ops.abft_matmul(x, w, mode="1s",
                             fault=FaultSpec.value(row, col, 100.0))
    res = np.asarray(chk.residual)                 # (gm, gn, bm)
    hot = np.unravel_index(np.argmax(res), res.shape)
    log("kernels", kernel="abft_matmul[1s]+fault", flag=bool(chk.flag),
        located_row=int(hot[0] * res.shape[2] + hot[2]), fault_row=row)
    check(bool(chk.flag), "injected accumulator fault is flagged")
    check(hot[0] * res.shape[2] + hot[2] == row, "fault row is located")

    L = 512
    q = normal(ks[2], (1, L, H, hd))
    k = normal(ks[3], (1, L, KV, hd))
    v = normal(ks[4], (1, L, KV, hd))
    o, chk = flash_ops.flash_attention(q, k, v, causal=True)
    ok, err, scale = close_bf16(o, attention_ref(q, k, v, causal=True))
    log("kernels", kernel="flash_attention", shape=[1, L, H, KV, hd],
        max_err=err, ref_scale=scale, flag=bool(chk.flag))
    check(ok, "flash_attention agrees with attention_ref")
    check(not bool(chk.flag), "clean flash_attention does not flag")

    S = MAX_LEN
    qd = normal(ks[5], (SLOTS, 1, H, hd))
    kc = normal(ks[6], (SLOTS, S, KV, hd))
    vc = normal(ks[7], (SLOTS, S, KV, hd))
    lengths = jax.random.randint(ks[8], (SLOTS,), 1, S + 1, jnp.int32)
    ref = attention_ref(qd, kc, vc, causal=False, lengths=lengths)
    o, chk = flash_ops.flash_decode(qd, kc, vc, lengths)
    ok, err, scale = close_bf16(o, ref)
    log("kernels", kernel="flash_decode", shape=[SLOTS, S, H, KV, hd],
        max_err=err, ref_scale=scale, flag=bool(chk.flag))
    check(ok, "flash_decode agrees with attention_ref")
    check(not bool(chk.flag), "clean flash_decode does not flag")

    bs = 16
    W = S // bs
    perm = jax.random.permutation(ks[9], SLOTS * W).astype(jnp.int32)
    tables = perm.reshape(SLOTS, W)
    # scatter the dense rows into a shuffled block pool
    pool_k = jnp.zeros((SLOTS * W, bs, KV, hd), bf16).at[perm].set(
        kc.reshape(SLOTS * W, bs, KV, hd))
    pool_v = jnp.zeros((SLOTS * W, bs, KV, hd), bf16).at[perm].set(
        vc.reshape(SLOTS * W, bs, KV, hd))
    o, chk = flash_ops.flash_decode_paged(qd, pool_k, pool_v, tables,
                                          lengths)
    ok, err, scale = close_bf16(o, ref)
    log("kernels", kernel="flash_decode_paged", block=bs,
        shape=[SLOTS, S, H, KV, hd], max_err=err, ref_scale=scale,
        flag=bool(chk.flag))
    check(ok, "flash_decode_paged agrees with attention_ref")
    check(not bool(chk.flag), "clean flash_decode_paged does not flag")


# -------------------------------------------------------------------- serve
def make_requests(cfg, seed: int, n: int = SLOTS) -> list:
    from repro.serve.engine import Request

    rng = np.random.default_rng(seed)
    lo, hi = PROMPT_LENS
    return [Request(uid=i,
                    prompt=rng.integers(1, cfg.vocab_size,
                                        size=int(rng.integers(lo, hi + 1))
                                        ).astype(np.int32),
                    max_new_tokens=NEW_TOKENS)
            for i in range(n)]


def build_engine(model, params, *, abft_mode: str = "auto", mesh=None,
                 cache_kind: str = "dense"):
    """The engine ``launch/serve.py`` builds for these flags."""
    from repro.launch.serve import abft_config, serving_dtype
    from repro.serve.engine import RecoveryPolicy, ServeEngine

    return ServeEngine(model, params, slots=SLOTS, max_len=MAX_LEN,
                       abft=abft_config(abft_mode), dtype=serving_dtype(),
                       policy=RecoveryPolicy(max_retries=1), mesh=mesh,
                       cache_kind=cache_kind)


def serve(engine, reqs, fault_at=None) -> tuple[dict, dict]:
    """Run the requests; return (streams by uid, stats summary)."""
    from repro.serve.scheduler import EngineStats

    engine.stats = EngineStats()
    t0 = time.perf_counter()
    out = engine.run(reqs, fault_at=fault_at)
    dt = time.perf_counter() - t0
    st = engine.stats
    summary = {
        "seconds": dt, "tokens": st.tokens,
        "smoke_tokens_per_s_not_a_metric": st.tokens / dt,
        "finished": sum(1 for r in reqs if r.done and r.error is None),
        "detections": st.faults_detected, "retries": st.retries,
        "hard_faults": st.hard_faults, "evictions": st.evictions,
        "errors": {r.uid: r.error for r in reqs if r.error},
    }
    return {uid: list(map(int, toks)) for uid, toks in out.items()}, summary


def check_clean(summary: dict, n: int, what: str) -> None:
    check(summary["finished"] == n, f"{what}: all {n} requests finish")
    check(summary["detections"] == 0, f"{what}: 0 detections")
    check(summary["errors"] == {}, f"{what}: 0 errors")


def plan_schemes(plan) -> dict:
    return {r["layer"]: r["scheme"] for r in plan.report_rows()}


def decode_hlo(engine) -> str:
    """Compiled HLO of the engine's decode step at its serving shapes."""
    from repro.models.layers import ModelFault

    toks = jnp.zeros((engine.slots, 1), jnp.int32)
    mask = jnp.ones((engine.slots,), bool)
    tables = (engine.pool.device_tables()
              if engine.pool is not None else None)
    return engine._decode.lower(
        engine.params, toks, engine.cache, jnp.asarray(engine.pos), mask,
        engine.keys, tables, ModelFault.none()).compile().as_text()


def kernel_sites(hlo: str) -> set:
    """(scheme, site) of every ``tpu_custom_call`` in compiled HLO, read
    off the ``abft[scheme][site]`` scope in its op metadata."""
    sites = set()
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.search(r"abft\[([^\]]+)\]\[([^\]]+)\]", line)
            if m:
                sites.add((m.group(1), m.group(2)))
    return sites


def check_kernels_in_decode(engine, what: str) -> None:
    plan = plan_schemes(engine.plan)
    want = {site for site, s in plan.items() if s == "block_1s"}
    found = kernel_sites(decode_hlo(engine))
    log(what, block_1s_plan_sites=sorted(want),
        tpu_custom_call_sites=sorted(f"{s}:{t}" for s, t in found))
    check(want, f"{what}: the decode plan selects block_1s somewhere")
    check(want <= {t for s, t in found if s == "block_1s"},
          f"{what}: tpu_custom_call at every block_1s decode site")


def prefill_logits(model, params, ctx, prompt) -> np.ndarray:
    """Logits of the first generated token, from one prefill call."""
    from repro.launch.serve import serving_dtype

    cache = model.init_cache(1, len(prompt), dtype=serving_dtype())
    fn = jax.jit(lambda p, t, c: model.prefill(p, {"tokens": t}, c, ctx)[0])
    out = fn(params, jnp.asarray(prompt, jnp.int32)[None], cache)
    return np.asarray(out, np.float32)[0, 0]


def f32_logits(model, params, prompt) -> np.ndarray:
    """The same logits computed exactly enough to measure bf16 rounding:
    the bf16 weights cast to f32, every dot at full f32 precision, no
    ABFT."""
    from repro.core.protected import ABFTConfig
    from repro.models.layers import LayerCtx

    p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    cache = model.init_cache(1, len(prompt), dtype=jnp.float32)
    ctx = LayerCtx(abft=ABFTConfig.off())
    with jax.default_matmul_precision("highest"):
        fn = jax.jit(
            lambda p, t, c: model.prefill(p, {"tokens": t}, c, ctx)[0])
        out = fn(p32, jnp.asarray(prompt, jnp.int32)[None], cache)
    return np.asarray(out, np.float32)[0, 0]


def agree_bf16(logits: dict, ref) -> tuple[bool, dict]:
    """Do two bf16 computations of the same logits, ``{name: logits}``
    with the baseline second, agree within bf16 tolerance?  The tolerance
    is measured, not assumed: each lies within its bf16 rounding error of
    the f32 reference ``ref``, so they may differ by twice the baseline's
    error."""
    (na, a), (nb, b) = logits.items()
    err = float(np.max(np.abs(a - b)))
    tol = 2 * float(np.max(np.abs(b - ref)))
    return err <= tol, {
        "max_abs_diff": err, "bf16_tolerance": tol,
        "max_abs_err_vs_f32": {n: float(np.max(np.abs(v - ref)))
                               for n, v in logits.items()},
        "logit_scale": float(np.max(np.abs(ref))),
        "same_argmax": bool(np.argmax(a) == np.argmax(b))}


def phase_serve(model, params, seed: int) -> None:
    from repro.core.protected import ABFTConfig
    from repro.launch.serve import INJECT_STEP, injected_fault
    from repro.models.layers import LayerCtx

    cfg = model.cfg
    engine = build_engine(model, params)
    reqs = make_requests(cfg, seed)
    lpad = -(-max(len(r.prompt) for r in reqs) // 8) * 8
    prefill_plan = model.protection_plan(
        engine.abft.hw, engine.abft.effective_policy(), phase="prefill",
        n_tokens=len(reqs) * lpad)
    log("serve", prompts=[len(r.prompt) for r in reqs],
        hardware=engine.abft.hw.name,
        decode_plan=plan_schemes(engine.plan),
        prefill_plan=plan_schemes(prefill_plan))
    check("global" in plan_schemes(prefill_plan).values(),
          "prefill plan selects global")
    check("block_1s" in plan_schemes(engine.plan).values(),
          "decode plan selects block_1s")

    clean, summary = serve(engine, reqs)
    log("serve", run="clean (first run: compile included)", **summary)
    check_clean(summary, len(reqs), "clean run")
    check_kernels_in_decode(engine, "serve")

    reqs = make_requests(cfg, seed)
    faulted, summary = serve(engine, reqs,
                             fault_at=(INJECT_STEP, injected_fault()))
    landed = engine.stats.injection_log
    log("faults", run="injected", injection_log=landed, **summary)
    check(summary["detections"] >= 1, "injected fault is detected")
    check(summary["retries"] >= 1, "detection is retried")
    check(summary["hard_faults"] == 0, "0 hard faults")
    check(summary["evictions"] == 0 and summary["errors"] == {},
          "no evictions or errors")
    check(summary["finished"] == len(reqs), "all requests finish")
    check(faulted == clean, "greedy streams byte-identical to clean run")
    log("faults", streams_identical=True)

    prompt = reqs[0].prompt
    on = prefill_logits(model, params, LayerCtx(abft=engine.abft), prompt)
    off = prefill_logits(model, params, LayerCtx(abft=ABFTConfig.off()),
                         prompt)
    ok, stats = agree_bf16({"protected": on, "abft_off": off},
                           f32_logits(model, params, prompt))
    log("faults", first_step_logits="protected vs abft off", **stats)
    check(ok, "first-step logits agree within bf16 tolerance")


# --------------------------------------------------------------------- mesh
def shard_devices(arr) -> list:
    return [str(s.device) for s in arr.addressable_shards]


def phase_mesh(model, params, seed: int, width: int) -> None:
    from repro.models.layers import LayerCtx

    cfg = model.cfg
    check(len(jax.devices()) >= width, f"{width} devices present")
    eng_n = build_engine(model, params, mesh=width, cache_kind="paged")
    wq = eng_n.params["segments"]
    leaf = jax.tree_util.tree_leaves(wq)[0]
    embed = eng_n.params["embed"]
    pool = jax.tree_util.tree_leaves(eng_n.cache)[0]
    devs = {name: shard_devices(a) for name, a in
            (("embed", embed), ("first_segment_leaf", leaf),
             ("kv_pool", pool))}
    log("mesh", width=width, shard_devices=devs,
        embed_shard_shape=list(embed.addressable_shards[0].data.shape),
        embed_shape=list(embed.shape),
        plan=plan_schemes(eng_n.plan))
    check(len(set(devs["embed"])) == width,
          f"embed shards land on {width} distinct devices")
    check(embed.addressable_shards[0].data.shape[0] * width
          == embed.shape[0], "embed is split over the model axis")
    check(len(set(devs["kv_pool"])) == width,
          f"kv pool shards land on {width} distinct devices")

    reqs_n = make_requests(cfg, seed)
    streams_n, summary = serve(eng_n, reqs_n)
    log("mesh", run=f"mesh={width} paged", **summary)
    check_clean(summary, len(reqs_n), f"mesh={width}")
    check_kernels_in_decode(eng_n, "mesh")

    eng_1 = build_engine(model, params, mesh=1, cache_kind="paged")
    reqs_1 = make_requests(cfg, seed)
    streams_1, summary = serve(eng_1, reqs_1)
    log("mesh", run="mesh=1 paged", **summary)
    check_clean(summary, len(reqs_1), "mesh=1")

    if streams_n == streams_1:
        log("mesh", streams_identical=True)
        return
    # first divergence: the earliest step index over all requests
    first = min(
        (next(i for i, (a, b) in enumerate(zip(streams_n[u], streams_1[u]))
              if a != b), u)
        for u in streams_n if streams_n[u] != streams_1[u])
    step, uid = first
    req = next(r for r in reqs_1 if r.uid == uid)
    ctx_tokens = np.concatenate(
        [req.prompt, np.asarray(streams_1[uid][:step], np.int32)])
    ln = prefill_logits(model, eng_n.params, LayerCtx(abft=eng_n.abft),
                        ctx_tokens)
    l1 = prefill_logits(model, eng_1.params, LayerCtx(abft=eng_1.abft),
                        ctx_tokens)
    ok, stats = agree_bf16({f"mesh={width}": ln, "mesh=1": l1},
                           f32_logits(model, eng_1.params, ctx_tokens))
    top = np.argsort(-l1)[:2]
    log("mesh", streams_identical=False, first_divergence_uid=uid,
        first_divergence_step=step,
        tokens={f"mesh={width}": streams_n[uid][step],
                "mesh=1": streams_1[uid][step]},
        mesh1_top2={int(t): float(l1[t]) for t in top},
        meshn_at_those={int(t): float(ln[t]) for t in top}, **stats)
    check(ok, "logits at the first divergence agree within bf16 tolerance")


# --------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", type=int, default=None,
                    help="run only the sharded-serving path on this many "
                         "chips (4), compared against one device")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}. "
              "Rehearse on CPU with: python -m repro.launch.serve "
              "--scale smoke", file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.core.hardware import device_hardware
    from repro.launch.serve import serving_dtype
    from repro.models import build_model

    count = len(jax.devices())
    log("device", platform=dev.platform, kind=dev.device_kind, count=count,
        hardware=device_hardware().name, compile_cache=cache_dir)
    cfg = get_config(ARCH)
    dtype = serving_dtype()
    t0 = time.perf_counter()
    model = build_model(cfg)
    # one compiled program: op-by-op init of 2.5 GB takes about a minute
    params = jax.jit(model.init_params, static_argnames="dtype")(
        jax.random.PRNGKey(args.seed), dtype=dtype)
    jax.block_until_ready(params)
    log("model", arch=cfg.name, n_layers=cfg.n_layers, d_model=cfg.d_model,
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, d_ff=cfg.d_ff,
        vocab=cfg.vocab_size, dtype=jnp.dtype(dtype).name,
        param_bytes=sum(a.nbytes for a in jax.tree_util.tree_leaves(params)),
        init_seconds=time.perf_counter() - t0)

    if args.mesh:
        phase_mesh(model, params, args.seed, args.mesh)
    else:
        phase_kernels(cfg, args.seed)
        phase_serve(model, params, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
