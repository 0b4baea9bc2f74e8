"""End-to-end driver (the paper's kind: network-attached inference).

Spins up the CRC-framed socket service, provisions ResNet-18 over the wire
(RIMFS image + RCB program — the paper's remote provisioning flow), streams
batched requests, and prints the latency/CV telemetry that Table 3 reports.

    PYTHONPATH=src python examples/serve_resnet18.py [n_requests]
"""
import sys
import time

import numpy as np

import jax

from repro.configs.resnet18 import CONFIG
from repro.core import rctc
from repro.models import resnet as rn
from repro.serving.server import Client, InferenceServer

n_requests = int(sys.argv[1]) if len(sys.argv) > 1 else 32
batch = 4

cfg = CONFIG.smoke()
params = rn.init_resnet(jax.random.PRNGKey(0), cfg)
prog, image = rctc.compile_resnet18(cfg, rn.fold_bn(params), batch=batch)

server = InferenceServer()
addr = server.start()
print(f"serving on {addr}")
try:
    client = Client(addr)
    print("provision:", client.provision(image, prog.encode()))
    rng = np.random.RandomState(0)
    ref_match = 0
    t0 = time.perf_counter()
    for i in range(n_requests):
        x = rng.rand(batch, cfg.image_size, cfg.image_size, 3) \
            .astype(np.float32)
        out = client.infer(input=x)["output"]
        ref = np.asarray(rn.resnet_forward(cfg, params, x))
        ref_match += int(np.allclose(out, ref, atol=1e-5))
    dt = time.perf_counter() - t0
    tel = client.telemetry()
    print(f"{n_requests} requests x batch {batch}: "
          f"{n_requests*batch/dt:.1f} img/s | "
          f"mean={tel['mean']*1e3:.2f} ms  CV={tel['cv_percent']:.2f}%  "
          f"p99={tel['p99']*1e3:.2f} ms")
    print(f"responses matching local oracle: {ref_match}/{n_requests}")
    client.close()
finally:
    server.stop()
if ref_match != n_requests:
    sys.exit(1)
