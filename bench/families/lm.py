"""Token generation behind ``InferenceServer`` with ``PagedServingEngine``.

Set-up: the configuration's reference module makes the weights on the
device from the seed in the type they are served in; the engine is built
with the configuration's ``engine`` settings; warm-up compiles (or loads
from the persistent cache) every prefill and decode program that the mix's
lengths can reach, and the eager sampling op of every prefill group size.

With ``observe`` (traced runs) the engine is a subclass that logs, for each
prefill and decode dispatch, its host time and sizes: the counts that the
per-layer readers turn into tokens, bytes and FLOPs. The served path is
otherwise the program's own.

The check: once the window has closed and the engine's state is freed,
requests finished in the window are drawn from the seed, the one with the
most served tokens among them, and the reference scores every served token
teacher-forced (``score``); the number compared is the widest gap.
"""
from __future__ import annotations

import gc
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.launch.steps import sample_tokens
from repro.models import transformer as tf
from repro.serving import paged_engine as pe
from repro.serving import protocol as proto
from repro.serving.server import InferenceServer

from harness import traffic

CHECK_REQUESTS = 6     # drawn from the seed, plus the longest reply


def program_config(cfg: dict) -> ModelConfig:
    """The program's ModelConfig for a configuration file (HF key names)."""
    return ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qkv_bias=cfg["qkv_bias"], rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        norm_eps=float(cfg["rms_norm_eps"]), dtype=cfg["torch_dtype"])


class ObservedEngine(pe.PagedServingEngine):
    """The paged engine, logging each dispatch it builds or looks up."""

    log: list

    def _prefill_exe(self, plen, batch, width):
        self.log.append(("prefill", time.perf_counter(), plen, batch))
        return super()._prefill_exe(plen, batch, width)

    def _decode_exe(self, batch_args):
        live = [i for i, r in enumerate(self._slots) if r is not None]
        ctx = sum(int(self._pos[i]) for i in live)
        self.log.append(("decode", time.perf_counter(), len(live),
                         batch_args[2], ctx))
        return super()._decode_exe(batch_args)


class Session:
    def __init__(self, cfg: dict, ref, seed: int, mix: dict,
                 observe: bool = False):
        self.cfg, self.ref, self.seed, self.mix = cfg, ref, seed, mix
        eng = cfg["engine"]
        self.pcfg = program_config(cfg)
        self.params = jax.block_until_ready(ref.init_params(cfg, seed))
        want = jax.tree.map(lambda s: tuple(s.shape), tf.model_specs(
            self.pcfg), is_leaf=lambda s: hasattr(s, "shape"))
        have = jax.tree.map(lambda a: tuple(a.shape), self.params)
        if want != have:
            raise ValueError("reference layout differs from the program's")
        cls = ObservedEngine if observe else pe.PagedServingEngine
        self.engine = cls(self.pcfg, self.params,
                          max_batch=int(eng["max_batch"]),
                          max_seq=int(eng["max_seq"]),
                          block_size=int(eng["block_size"]))
        self.engine.log = []
        self.server = InferenceServer(engine=self.engine)
        self.address = self.server.start()
        req = mix["request"]
        self.prompt_lens = traffic.lengths_reachable(req["prompt"])
        self.max_new = int(req["output"]["max"])
        self.length = max(self.prompt_lens) + self.max_new

    # ------------------------------------------------------------- warm-up
    def shapes(self) -> tuple:
        """Every (prompt length, group, width) prefill and (bucket, span,
        window) decode program the mix can reach, as the engine picks them
        (``PagedServingEngine._admit`` and ``step``)."""
        e = self.engine
        groups = [1 << i for i in range(e.max_batch.bit_length())
                  if 1 << i <= e.max_batch]
        prefill = [(p, g, e.cache.blocks_needed(p))
                   for p in self.prompt_lens for g in groups]
        buckets = sorted({pe._pow2_at_least(n)
                          for n in range(1, e.max_batch + 1)})
        lo = pe._pow2_at_least(e.cache.blocks_needed(min(self.prompt_lens)
                                                     + 1))
        hi = min(e.blocks_per_seq, pe._pow2_at_least(
            e.cache.blocks_needed(self.length)))
        spans = [s for s in (1 << i for i in range(hi.bit_length()))
                 if lo <= s <= hi]
        decode = [(b, s, w) for b in buckets for s in spans
                  for w in pe.DECODE_WINDOWS]
        return prefill, decode

    def warm(self) -> int:
        prefill, decode = self.shapes()
        e = self.engine
        for p, g, w in prefill:
            e._prefill_exe(p, g, w)
        dev = jax.devices()[0]
        for g in sorted({g for _, g, _ in prefill}):
            # committed to the device, as a prefill's logits are
            logits = jax.device_put(jnp.zeros((g, self.cfg["vocab_size"]),
                                              self.pcfg.dtype), dev)
            np.asarray(sample_tokens(logits, True, 1.0))
        for args in decode:
            e._decode_exe(args)
        return len(prefill) + len(decode)

    # ---------------------------------------------------------------- load
    def payload(self, spec) -> bytes:
        toks = traffic.prompt_tokens(self.seed, spec.index, spec.prompt_len,
                                     self.cfg["vocab_size"])
        return proto.pack_tensors({"prompt": toks,
                                   "__max_new": np.int32(spec.max_new)})

    def decode(self, body: bytes):
        toks = proto.unpack_tensors(body)["tokens"]
        return int(toks.shape[0]), toks

    def counters(self) -> dict:
        return {"processed": self.server._loop.stats["processed"]}

    def stop(self) -> None:
        self.server.stop()
        self.engine.close()
        self.engine.cache.k = self.engine.cache.v = None
        self.server = self.engine = None
        gc.collect()

    # --------------------------------------------------------------- check
    def _sample(self, records: list, seed: int) -> list:
        """Requests finished in the window, drawn from the seed, and the
        one with the most served tokens."""
        done = [r for r in records if r.ok]
        if not done:
            return []
        rng = traffic.seed_rng(seed, 6)
        pick = set(rng.choice(len(done), min(CHECK_REQUESTS, len(done)),
                              replace=False).tolist())
        pick.add(max(range(len(done)), key=lambda i: done[i].units))
        return [done[i] for i in sorted(pick)]

    def _gaps(self, records: list, seed: int, control: bool) -> dict:
        lim = self.cfg["limits"]["token_logit_gap"]
        gap, n = 0.0, 0
        sample = self._sample(records, seed)
        for r in sample:
            prompt = traffic.prompt_tokens(self.seed, r.spec.index,
                                           r.spec.prompt_len,
                                           self.cfg["vocab_size"])
            served = np.asarray(r.out, np.int32)
            if len(served) != r.spec.max_new + 1:
                return {"token_logit_gap": (float("inf"), "<=", lim),
                        "reply_length_ok": (0, ">=", 1)}
            g = self.ref.score(self.cfg, self.params, prompt, served,
                               self.length, control=control)
            gap, n = max(gap, float(g.max())), n + len(served)
        if not sample:
            gap = float("inf")
        return {"token_logit_gap": (gap, "<=", lim),
                "tokens_checked": (n, ">=", 1)}

    def check(self, records: list, seed: int) -> dict:
        return self._gaps(records, seed, control=False)

    def control(self, records: list, seed: int) -> dict:
        """The check's number for the control: at the same prompts and
        served tokens, the gap of the token that float8 puts first."""
        return self._gaps(records, seed, control=True)
