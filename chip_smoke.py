"""Serve full-width ResNet-18 and qwen2-1.5b on one TPU, and check the replies.

One process: the server and its clients are threads, and nothing is
spawned, so this process alone holds the chip. Both phases go through
the entry points of ``python -m repro.launch.serve``:

* resnet18: ``ResNetConfig()`` (224x224, widths 64-512, 1000 classes)
  from ``--seed``, BN folded, lowered by ``rctc.compile_resnet18`` at
  batch 4 and PROVISIONed over the socket. One solo request and
  ``InferenceServer.warm`` compile the linked path and every batch
  bucket, then 32 requests arrive from 4 connections at pipeline depth
  2, so solo linked dispatches and coalesced ``run_batched`` dispatches
  both serve. Every reply is compared with ``rn.resnet_forward`` on the
  unfolded weights in float32 under ``"highest"`` on the same chip.
* lm: qwen2-1.5b at its published widths in bfloat16, served by
  ``PagedServingEngine(max_batch=4, max_seq=512, block_size=16)`` behind
  ``InferenceServer``; 4 prompts of 128 tokens with ``max_new=16``
  through ``Client.infer``. Each reply must hold ``max_new + 1`` tokens.
  One ``tf.forward_full`` on float32 copies of the weights (jnp
  attention, ``"highest"``), teacher-forced on each prompt and its served
  tokens, is the reference: the engine's compiled paged prefill must
  match its last prompt position, and every served token must be a
  near-argmax of its row, which checks the paged decode.

An error reply, a busy or shed verdict, a watchdog preemption, a gap over
its tolerance, or a device that is not a TPU exits non-zero. The last
line of standard output is one JSON object naming the device.

    python chip_smoke.py [--seed 0] [--phase resnet18|lm]
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.resnet18 import ResNetConfig  # noqa: E402
from repro.kernels import registry  # noqa: E402
from repro.kernels.common import resolve_interpret  # noqa: E402
from repro.launch import compile_cache, serve  # noqa: E402
from repro.models import resnet as rn  # noqa: E402
from repro.models import transformer as tf  # noqa: E402
from repro.serving.server import Client  # noqa: E402

# On a TPU, float32 convolutions and matmuls at the default precision
# round their operands to bfloat16, so the served float32 program is not
# float32 exact against the "highest" reference. The replies are softmax
# probabilities, and the top class holds most of the mass, so they are
# compared as log-probabilities, where every class counts alike (below
# LOG_FLOOR, a probability counts as the floor). Bound on max |log served
# - log reference| over all 1000 classes: about 2.6x the 2.28e-1
# measured on a TPU v5e at seed 0.
RESNET_TOL = 0.6
LOG_FLOOR = 1e-30
# The served LM runs in bfloat16 (weights, activations, KV pool); the
# reference in float32 under "highest". Bound on max |served - reference|
# over the prefill's last-position logits (max |logit| about 4.7): about
# 2.8x the 7.06e-2 measured on a TPU v5e at seed 0.
LM_TOL = 0.2
# A greedy token is the argmax of served logits; if each logit is within
# LM_TOL of the reference, the token's reference logit is within 2 LM_TOL
# of its row's maximum.
DECODE_TOL = 2 * LM_TOL

RESNET_BATCH, RESNET_REQUESTS, RESNET_CLIENTS, RESNET_PIPELINE = 4, 32, 4, 2
LM_PROMPTS, LM_PROMPT_LEN, LM_MAX_NEW = 4, 128, 16


def say(msg: str) -> None:
    print(msg, flush=True)


def counters(client) -> dict:
    """Dispatcher counters the smoke reports and holds to zero."""
    tel = client.telemetry()
    srv = tel["serving"]
    return {"processed": srv["processed"], "rejected": srv["rejected"],
            "shed": srv["shed"], "errors": srv["errors"],
            "batched_dispatches": srv["batched"]["dispatches"],
            "batched_requests": srv["batched"]["requests"],
            "watchdog_preemptions":
                tel["counters"].get("watchdog_preemptions", 0)}


def check_clean(c: dict) -> None:
    bad = {k: c[k] for k in ("rejected", "shed", "errors",
                             "watchdog_preemptions") if c[k]}
    if bad:
        raise SystemExit(f"FAIL: dispatcher counters not clean: {bad}")


def phase_resnet(seed: int) -> None:
    cfg = ResNetConfig()
    t0 = time.perf_counter()
    params, server, client = serve.start_resnet(cfg, seed, RESNET_BATCH)
    try:
        addr = server.address
        serve.drive_resnet(addr, cfg, RESNET_BATCH, 1, 1, 1, seed)
        t1 = time.perf_counter()
        buckets = server.warm()
        warm = time.perf_counter() - t1
        setup = time.perf_counter() - t0
        before = counters(client)
        t0 = time.perf_counter()
        pairs = serve.drive_resnet(addr, cfg, RESNET_BATCH, RESNET_REQUESTS,
                                   RESNET_CLIENTS, RESNET_PIPELINE, seed + 1)
        served = time.perf_counter() - t0
        after = counters(client)
        client.close()
    finally:
        server.stop()

    with jax.default_matmul_precision("highest"):
        ref_fn = jax.jit(lambda x: rn.resnet_forward(cfg, params, x))
        refs = [np.asarray(ref_fn(x)) for x, _ in pairs]
    gap = max(float(np.max(np.abs(log_p(out) - log_p(ref))))
              for (_, out), ref in zip(pairs, refs))
    top = max(float(np.max(ref)) for ref in refs)
    floored = sum(int(np.sum(ref < LOG_FLOOR)) for ref in refs)
    batched = after["batched_requests"] - before["batched_requests"]
    solo = len(pairs) - batched
    say(f"[resnet18] set-up {setup:.3f}s, of which {warm:.3f}s compiled "
        f"batch buckets {buckets}; served {len(pairs)} requests x batch "
        f"{RESNET_BATCH} in {served:.3f}s: solo={solo} batched={batched} "
        f"in {after['batched_dispatches'] - before['batched_dispatches']} "
        f"dispatches")
    say(f"[resnet18] max |log served - log f32 highest| = {gap:.6e} over "
        f"all classes (tolerance {RESNET_TOL:.1e}; reference max "
        f"probability {top:.4f}; {floored} of {sum(r.size for r in refs)} "
        f"reference probabilities under the {LOG_FLOOR:.0e} floor)")
    say(f"[resnet18] dispatcher {after}")
    say(f"[resnet18] kernel registry stats {dict(registry.REGISTRY.stats)}")
    check_clean(after)
    if len(pairs) != RESNET_REQUESTS:
        raise SystemExit(f"FAIL: {len(pairs)}/{RESNET_REQUESTS} replies")
    if solo == 0 or batched == 0:
        raise SystemExit(f"FAIL: want solo and batched dispatches, got "
                         f"solo={solo} batched={batched}")
    if not gap <= RESNET_TOL:
        raise SystemExit(f"FAIL: resnet18 gap {gap} > {RESNET_TOL}")


def log_p(p: np.ndarray) -> np.ndarray:
    return np.log(np.maximum(p, LOG_FLOOR))


def prefill_logits(engine, prompts: list) -> np.ndarray:
    """Last-position logits of the engine's own compiled paged prefill for
    ``prompts`` (all one length), on blocks allocated for the check and
    released after it."""
    cache, plen = engine.cache, prompts[0].shape[0]
    seqs = [-(i + 1) for i in range(len(prompts))]
    for s in seqs:
        cache.allocate(s, tokens=plen)
    width = cache.blocks_needed(plen)
    fn = engine._prefill_exe(plen, len(prompts), width)
    logits, cache.k, cache.v = fn(
        engine.params, cache.k, cache.v,
        {"inputs": np.stack(prompts),
         "tables": cache.table_array(seqs, width=width)})
    for s in seqs:
        cache.release(s)
    return np.asarray(logits.astype(jnp.float32))


def phase_lm(seed: int) -> None:
    cfg = get_config("qwen2-1.5b")
    t0 = time.perf_counter()
    params, engine, server = serve.start_lm(cfg, seed, max_batch=4,
                                            max_seq=512, block_size=16)
    jax.block_until_ready(params)
    setup = time.perf_counter() - t0
    prompts = serve.lm_prompts(cfg, LM_PROMPTS, LM_PROMPT_LEN, seed)
    try:
        t0 = time.perf_counter()
        outs = serve.drive_lm(server.address, prompts, LM_MAX_NEW)
        served = time.perf_counter() - t0
        client = Client(server.address)
        after = counters(client)
        client.close()
    finally:
        server.stop()
    got = prefill_logits(engine, prompts)
    lens = [len(o) for o in outs]
    if lens != [LM_MAX_NEW + 1] * LM_PROMPTS:
        raise SystemExit(f"FAIL: reply lengths {lens}, want "
                         f"{LM_MAX_NEW + 1} each")

    # teacher-forced: row i scores the token served at step i
    forced = np.stack([np.concatenate([p, np.asarray(o[:-1], np.int32)])
                       for p, o in zip(prompts, outs)])
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, t: tf.forward_full(cfg32, p, t)[0][
            :, LM_PROMPT_LEN - 1:])(params32, jnp.asarray(forced))
        ref = np.asarray(ref)
    del params32                        # ~6.2 GB beside 3.1 GB of bf16
    gc.collect()
    gap = float(np.max(np.abs(got - ref[:, 0])))
    toks = np.asarray(outs)
    deficit = float(np.max(ref.max(-1) - np.take_along_axis(
        ref, toks[..., None], -1)[..., 0]))
    exact = int(np.sum(ref.argmax(-1) == toks))
    say(f"[lm] {cfg.name} {cfg.dtype} set-up {setup:.3f}s; served {len(outs)} "
        f"prompts x {LM_PROMPT_LEN} tokens, max_new {LM_MAX_NEW}, in "
        f"{served:.3f}s (compile included); reply lengths {lens}")
    say(f"[lm] prefill last-position logits: max |served - f32 highest| = "
        f"{gap:.6e} (tolerance {LM_TOL:.1e}; reference max |logit| "
        f"{float(np.max(np.abs(ref[:, 0]))):.4f})")
    say(f"[lm] served tokens, teacher-forced f32 highest: {exact}/"
        f"{toks.size} are its argmax; max (row max - served token's "
        f"logit) = {deficit:.6e} (tolerance {DECODE_TOL:.1e})")
    say(f"[lm] dispatcher {after}; kv {engine.kv_stats()}")
    say(f"[lm] kernel registry stats {dict(registry.REGISTRY.stats)}")
    check_clean(after)
    if not gap <= LM_TOL:
        raise SystemExit(f"FAIL: lm prefill gap {gap} > {LM_TOL}")
    if not deficit <= DECODE_TOL:
        raise SystemExit(f"FAIL: a served token is {deficit} below its "
                         f"row's max > {DECODE_TOL}")
    engine.close()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--phase", choices=("resnet18", "lm"), action="append",
                    help="run only this phase (repeatable); default both")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        sys.exit(2)
    cache_dir = compile_cache.enable()
    if resolve_interpret() is not False:
        raise SystemExit("FAIL: Pallas kernels would run in interpret mode")
    say(f"[device] {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"jax {jax.__version__}; compile cache {cache_dir}")
    phases = args.phase or ["resnet18", "lm"]
    if "resnet18" in phases:
        phase_resnet(args.seed)
    if "lm" in phases:
        phase_lm(args.seed)
    entries = sum(1 for _ in Path(cache_dir).glob("*")) \
        if Path(cache_dir).is_dir() else 0
    say(f"[device] compile cache {cache_dir}: {entries} entries")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
