"""ResNet image classification behind ``InferenceServer``'s RCB path.

Set-up: the configuration's reference module makes the weights on the
device from the seed; ``rctc.compile_resnet18`` lowers the BN-folded network
to an RCB program, which is PROVISIONed over the socket with its weight
image; one solo request and ``InferenceServer.warm`` compile the linked
handlers and every batch bucket the window can form. Requests carry one of
a pool of image batches made from the seed; every reply due in the window
is checked against the reference on its batch.
"""
from __future__ import annotations

import numpy as np

import jax

from repro.configs.resnet18 import ResNetConfig
from repro.core import rctc
from repro.models import resnet as rn
from repro.serving import protocol as proto
from repro.serving.server import Client, InferenceServer

from harness import traffic

POOL = 16          # distinct image batches a run sends, made from the seed


class Session:
    def __init__(self, cfg: dict, ref, seed: int, mix: dict,
                 observe: bool = False):
        eng = cfg["engine"]
        self.cfg, self.ref, self.seed = cfg, ref, seed
        self.batch = int(eng["images_per_request"])
        if mix["request"]["images"] != self.batch:
            raise ValueError(f"mix sends {mix['request']['images']} images "
                             f"a request; the program is built for "
                             f"{self.batch}")
        rcfg = ResNetConfig(
            name=cfg["name"], stage_sizes=tuple(cfg["stage_sizes"]),
            stage_widths=tuple(cfg["stage_widths"]),
            num_classes=cfg["num_classes"], image_size=cfg["image_size"],
            stem_width=cfg["stem_width"])
        # the precision the configuration states, for every program the
        # process compiles from here on (the server's handlers included)
        jax.config.update("jax_default_matmul_precision",
                          cfg["matmul_precision"])
        self.params = jax.block_until_ready(ref.init_params(cfg, seed))
        host = jax.tree.map(np.asarray, self.params)
        prog, image = rctc.compile_resnet18(rcfg, rn.fold_bn(host),
                                            batch=self.batch)
        self.server = InferenceServer(batch_window=int(eng["batch_window"]))
        self.client = Client(self.server.start())
        self.address = self.server.address
        self.client.provision(image, prog.encode())
        rng = traffic.seed_rng(seed, 5)
        s = cfg["image_size"]
        self.inputs = [rng.random((self.batch, s, s, 3), np.float32)
                       for _ in range(POOL)]
        self.bodies = [proto.pack_tensors({"input": x}) for x in self.inputs]

    def warm(self) -> int:
        """Compile the solo linked path and every batch bucket; returns the
        number of shapes warmed."""
        self.client.infer(input=self.inputs[0])
        return 1 + len(self.server.warm())

    # --------------------------------------------------------------- load
    def payload(self, spec) -> bytes:
        return self.bodies[spec.index % POOL]

    def decode(self, body: bytes):
        out = proto.unpack_tensors(body)["output"]
        return int(out.shape[0]), out

    def counters(self) -> dict:
        srv = self.server
        return {"processed": srv._loop.stats["processed"],
                "batched_requests": srv.batched_stats["requests"],
                "batched_dispatches": srv.batched_stats["dispatches"],
                "dma_crc_checked":
                    srv.executor.driver.stats.get("dma_crc_checked", 0),
                "dma_bytes": srv.executor.driver.stats.get("dma_bytes", 0)}

    def stop(self) -> None:
        try:
            self.client.close()
        finally:
            self.server.stop()
        self.server = self.client = None

    # -------------------------------------------------------------- check
    def check(self, records: list, seed: int) -> dict:
        """Widest gap, over every class of every reply due in the window, of
        the served log-probability from the reference's."""
        replies = [r for r in records if r.ok]
        used = sorted({r.spec.index % POOL for r in replies})
        refs = {i: self.ref.log_probs(self.cfg, self.params,
                                      self.inputs[i]) for i in used}
        gap = 0.0
        for r in replies:
            got = self.ref.floor_log(np.asarray(r.out, np.float64))
            gap = max(gap, float(np.max(np.abs(
                got - refs[r.spec.index % POOL]))))
        lim = self.cfg["limits"]["logp_gap"]
        return {"logp_gap": (gap if replies else float("inf"), "<=", lim)}

    def control(self, records: list, seed: int) -> dict:
        """The check's number for the control: the reference computed one
        precision step down (``"high"``, three bfloat16 passes) put in the
        program's place, on the batches the window sent."""
        used = sorted({r.spec.index % POOL for r in records if r.ok})
        gap = 0.0
        for i in used:
            low = self.ref.log_probs(self.cfg, self.params, self.inputs[i],
                                     control=True)
            ref = self.ref.log_probs(self.cfg, self.params, self.inputs[i])
            gap = max(gap, float(np.max(np.abs(low - ref))))
        return {"logp_gap": (gap, "<=", self.cfg["limits"]["logp_gap"])}
