"""Serving driver: network-attached inference service (the paper's mode).

Starts the CRC-framed socket server, provisions a model over the socket
and fires client requests at it -- optionally from several concurrent
connections, each pipelining v2 request-id frames -- then reports
latency and dispatcher telemetry. ``--model`` names the configuration:
``resnet18`` (224x224, widths 64-512, 1000 classes) serves the RCB
program through the batch window; an LM configuration such as
``qwen2-1.5b`` serves ``PagedServingEngine`` behind the same server.
``-smoke`` variants are the reduced CPU sizes.

  PYTHONPATH=src python -m repro.launch.serve --model resnet18-smoke
  PYTHONPATH=src python -m repro.launch.serve --model resnet18 --clients 4
  PYTHONPATH=src python -m repro.launch.serve --model qwen2-1.5b-smoke
  PYTHONPATH=src python -m repro.launch.serve --fleet --requests 48

--fleet runs the elastic-operations demo: a FleetController scales the
live tile mesh up and back down, hot-swaps the weight image (probe +
atomic flip), and survives a tile-group kill -- all under the same
client traffic, with every response checked against a reference.
"""
from __future__ import annotations

import argparse
import threading
import time

import numpy as np

import jax

from repro.configs import get_config
from repro.configs.resnet18 import CONFIG as RESNET
from repro.configs.resnet18 import ResNetConfig
from repro.core import rctc
from repro.launch import compile_cache
from repro.models import resnet as rn
from repro.models import transformer as tf
from repro.models.common import init_params
from repro.serving.paged_engine import PagedServingEngine
from repro.serving.server import Client, InferenceServer


def model_config(name: str):
    """``resnet18[-smoke]`` or any registered LM configuration name."""
    if name.split("-")[0] == RESNET.name:
        return RESNET.smoke() if name.endswith("-smoke") else RESNET
    return get_config(name)


def _run_threads(target, n: int) -> None:
    """Run ``target(i)`` on ``n`` threads; re-raise the first failure."""
    errors: list = []

    def guarded(i: int) -> None:
        try:
            target(i)
        except Exception as e:          # re-raised on the calling thread
            errors.append(e)

    threads = [threading.Thread(target=guarded, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]


# ------------------------------------------------------------------ resnet
def start_resnet(cfg: ResNetConfig, seed: int = 0, batch: int = 4,
                 batch_window: int = 8):
    """Initialise ResNet-18 from ``seed``, lower it to an RCB program, start
    a server and PROVISION the image and program over the socket. Returns
    ``(params, server, client)``; ``params`` are the unfolded weights."""
    params = rn.init_resnet(jax.random.PRNGKey(seed), cfg)
    prog, image = rctc.compile_resnet18(cfg, rn.fold_bn(params),
                                        batch=batch)
    server = InferenceServer(batch_window=batch_window)
    try:
        client = Client(server.start())
        client.provision(image, prog.encode())
    except Exception:
        server.stop()
        raise
    return params, server, client


def drive_resnet(addr: tuple, cfg: ResNetConfig, batch: int, requests: int,
                 clients: int, pipeline: int, seed: int = 0) -> list:
    """Send ``requests`` image batches from ``clients`` connections, each
    keeping up to ``pipeline`` requests in flight. Returns one
    ``(input, output)`` pair per reply; an error reply raises."""
    # distribute the requests exactly: the first `requests % clients`
    # connections take one extra
    shares = [requests // clients + (1 if c < requests % clients else 0)
              for c in range(clients)]
    pairs: list = [[] for _ in range(clients)]

    def run_client(cid: int) -> None:
        client = Client(addr)
        rng = np.random.RandomState(seed * 1000 + cid)
        try:
            left = shares[cid]
            while left:
                sent = []
                for _ in range(min(pipeline, left)):
                    x = rng.rand(batch, cfg.image_size, cfg.image_size,
                                 3).astype(np.float32)
                    sent.append((x, client.infer_async(input=x)))
                for x, rid in sent:
                    pairs[cid].append((x, client.result(rid)["output"]))
                left -= len(sent)
        finally:
            client.close()

    _run_threads(run_client, clients)
    return [p for ps in pairs for p in ps]


def serve_resnet(cfg: ResNetConfig, requests: int, batch: int, clients: int,
                 pipeline: int, batch_window: int = 8) -> None:
    t0 = time.perf_counter()
    _, server, c0 = start_resnet(cfg, 0, batch, batch_window)
    print(f"[serve] {cfg.name} provisioned on {server.address}")
    try:
        drive_resnet(server.address, cfg, batch, 1, 1, 1)
        buckets = server.warm()
        print(f"[serve] set-up {time.perf_counter() - t0:.1f}s "
              f"(batch buckets {buckets} compiled)")
        t0 = time.perf_counter()
        n = len(drive_resnet(server.address, cfg, batch, requests, clients,
                             pipeline, 1))
        dt = time.perf_counter() - t0
        tel = c0.telemetry()
        srv = tel.get("serving", {})
        print(f"[serve] {n} requests x batch {batch} over {clients} "
              f"client(s) (pipeline depth {pipeline}): "
              f"{n*batch/dt:.1f} img/s; "
              f"CV={tel.get('cv_percent', 0):.2f}% "
              f"p99={tel.get('p99', 0)*1e3:.2f}ms; "
              f"dispatcher processed={srv.get('processed')} "
              f"rejected={srv.get('rejected')} shed={srv.get('shed')} "
              f"batched={srv.get('batched', {}).get('requests', 0)}reqs/"
              f"{srv.get('batched', {}).get('dispatches', 0)}dispatches "
              f"queue_wait_p95="
              f"{srv.get('queue_wait', {}).get('p95', 0)*1e3:.2f}ms")
        c0.close()
    finally:
        server.stop()


def serve_fleet(requests: int, groups: int = 2, peak: int = 8) -> None:
    """Elastic fleet demo: scale cycle + kill/heal + hot swap under
    sustained traffic, every response bit-compared to a single-device
    reference."""
    from repro.core import rhal, rimfs
    from repro.core.fleet import FleetController

    depth, n = 8, 24
    prog = rctc.compile_gemm_chain(depth, n)
    files = rctc.gemm_chain_weights(depth, n)
    image = rimfs.pack(files)
    server = InferenceServer(mesh=rhal.TileMesh(groups), max_queue=256)
    addr = server.start()
    print(f"[fleet] listening on {addr}, mesh={groups} groups")
    fleet = FleetController(server)
    ok = bad = 0
    try:
        client = Client(addr, retries=10, backoff=0.02, retry_seed=0)
        client.provision(image, prog.encode())
        x = np.random.RandomState(0).randn(n, n).astype(np.float32)
        ref = client.infer(input=x)

        def burst(count: int, label: str) -> None:
            nonlocal ok, bad
            t0 = time.perf_counter()
            for _ in range(count):
                out = client.infer(input=x)
                if all(np.array_equal(ref[k], out[k]) for k in ref):
                    ok += 1
                else:
                    bad += 1
            print(f"[fleet] {label}: {count} requests, "
                  f"{(time.perf_counter() - t0) / count * 1e3:.2f}ms avg, "
                  f"bit_identical={bad == 0}")

        share = max(4, requests // 4)
        burst(share, f"baseline @{groups}")
        rep = fleet.scale_to(peak)
        print(f"[fleet] scaled {rep['from']} -> {rep['to']} in "
              f"{rep['seconds'] * 1e3:.1f}ms")
        burst(share, f"scaled @{peak}")
        state = fleet.swap_weights(rimfs.pack(files), label="repack")
        print(f"[fleet] hot swap: {state}")
        burst(share, "post-swap")
        server.mesh.kill(peak - 1)
        rep = fleet.tick()
        print(f"[fleet] killed group {peak - 1}; tick -> "
              f"{rep['action']}")
        burst(share, "post-heal")
        rep = fleet.scale_to(groups)
        print(f"[fleet] scaled back -> {rep['to']} "
              f"(cached_mesh={rep.get('cached_mesh')})")
        print(f"[fleet] done: ok={ok} mismatched={bad} "
              f"events={dict(fleet.summary()['events'])}")
        client.close()
    finally:
        fleet.stop()
        server.stop()


# ---------------------------------------------------------------------- lm
def start_lm(cfg, seed: int = 0, max_batch: int = 4, max_seq: int = 512,
             block_size: int = 16):
    """Initialise the LM from ``seed`` and start ``PagedServingEngine``
    behind ``InferenceServer``. Returns ``(params, engine, server)``."""
    params = init_params(jax.random.PRNGKey(seed), tf.model_specs(cfg))
    engine = PagedServingEngine(cfg, params, max_batch=max_batch,
                                max_seq=max_seq, block_size=block_size)
    server = InferenceServer(engine=engine)
    server.start()
    return params, engine, server


def drive_lm(addr: tuple, prompts: list, max_new: int) -> list:
    """Send every prompt on its own connection, all at once, through
    ``Client.infer``. Returns the generated tokens per prompt, in order;
    an error, busy or shed reply raises."""
    outs: list = [None] * len(prompts)

    def run_client(i: int) -> None:
        client = Client(addr)
        try:
            outs[i] = client.infer(prompt=prompts[i],
                                   max_new=max_new)["tokens"]
        finally:
            client.close()

    _run_threads(run_client, len(prompts))
    return outs


def lm_prompts(cfg, n: int, length: int, seed: int = 0) -> list:
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, (length,)).astype(np.int32)
            for _ in range(n)]


def serve_lm(cfg, requests: int, prompt_len: int = 16,
             max_new: int = 8) -> None:
    t0 = time.perf_counter()
    _, engine, server = start_lm(cfg)
    print(f"[serve-lm] {cfg.name} set-up {time.perf_counter() - t0:.1f}s")
    try:
        prompts = lm_prompts(cfg, requests, prompt_len)
        t0 = time.perf_counter()
        outs = drive_lm(server.address, prompts, max_new)
        dt = time.perf_counter() - t0
        toks = sum(len(o) for o in outs)
        s = engine.telemetry.summary(warmup=2)
        print(f"[serve-lm] {requests} prompts x {prompt_len} tokens, "
              f"{toks} tokens in {dt:.2f}s ({toks/dt:.1f} tok/s, compile "
              f"included); decode-step CV={s.get('cv_percent', 0):.2f}%; "
              f"kv={engine.kv_stats()}")
    finally:
        server.stop()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet18-smoke",
                    help="resnet18[-smoke] or an LM config, e.g. "
                         "qwen2-1.5b[-smoke]")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4,
                    help="resnet: images per request")
    ap.add_argument("--clients", type=int, default=1,
                    help="resnet: concurrent client connections")
    ap.add_argument("--pipeline", type=int, default=4,
                    help="resnet: in-flight pipelined requests per "
                         "connection")
    ap.add_argument("--batch-window", type=int, default=8,
                    help="resnet: dispatcher coalescing window "
                         "(1 disables)")
    ap.add_argument("--fleet", action="store_true",
                    help="elastic fleet demo: scale cycle, hot swap, "
                         "kill/heal under traffic")
    ap.add_argument("--groups", type=int, default=2,
                    help="--fleet: starting mesh size")
    ap.add_argument("--peak", type=int, default=8,
                    help="--fleet: scale-cycle peak mesh size")
    args = ap.parse_args()
    compile_cache.enable()
    if args.fleet:
        serve_fleet(args.requests, groups=args.groups, peak=args.peak)
        return
    cfg = model_config(args.model)
    if isinstance(cfg, ResNetConfig):
        serve_resnet(cfg, args.requests, args.batch, args.clients,
                     args.pipeline, batch_window=args.batch_window)
    else:
        serve_lm(cfg, args.requests)


if __name__ == "__main__":
    main()
