"""Kernel registry — the Pallas kernels as first-class, autotuned handlers.

One ``KernelSpec`` per kernel holds the pallas implementation (the jitted
``ops.py`` wrapper), the pure-jnp reference oracle, the shape/dtype
contract, and an autotune space of block-size candidates. All three call
sites — eager model code, GRAPH_EXEC artifacts, and linked RCB kernel ops
(``Op.ATTENTION`` / ``MATMUL_INT8`` / ``SSM_SCAN`` / ``WKV6``) — pull
their implementation from here, so each hot loop has exactly one
implementation.

Dispatch (DESIGN.md §13):
  1. explicit ``impl`` override (``"pallas"`` | ``"ref"``) from op attrs
     or a keyword — tests, debugging, A/B rows;
  2. pallas with ``interpret`` resolved per call site OUTSIDE any trace
     (kernels/common.resolve_interpret: compiled on TPU, interpret-mode
     elsewhere);
  3. the ``ref.py`` oracle only where a spec's ``accepts`` refuses the
     shapes (non-causal attention on ragged tiles). ``stats`` counts
     every call under the implementation that actually ran.

Autotune: ``autotune()`` sweeps the spec's candidate block sizes on the
live backend and records the winner per (kernel, shape-sig, dtype,
backend). Winners persist as a RIMFS image — one JSON file at
``kernels/autotune.json`` — via ``pack_image``/``load_image``, so a
re-provisioned process performs ZERO sweep trials for shapes it has
already seen (``sweep_trials`` counts timed candidate runs and is the
testable witness).
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import rimfs as rimfs_mod
from repro.kernels.flash_attention import ops as _fa_ops
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.int8_matmul import ops as _im_ops
from repro.kernels.int8_matmul.ref import int8_matmul_ref
from repro.kernels.ssm_scan import ops as _ss_ops
from repro.kernels.ssm_scan.ref import ssm_scan_ref
from repro.kernels.wkv6 import ops as _wk_ops
from repro.kernels.wkv6.ref import wkv6_ref

AUTOTUNE_FILE = "kernels/autotune.json"
KERNEL_NAMES = ("attention", "matmul_int8", "ssm_scan", "wkv6")


def _divisor_leq(dim: int, cap: int) -> int:
    """Largest divisor of ``dim`` that is <= cap (>= 1)."""
    cap = max(1, min(int(cap), int(dim)))
    while dim % cap:
        cap -= 1
    return cap


def _dedup(cands: list[dict]) -> list[dict]:
    seen, out = set(), []
    for c in cands:
        key = tuple(sorted(c.items()))
        if key not in seen:
            seen.add(key)
            out.append(c)
    return out


# ---------------------------------------------------------------------------
# Per-kernel adapters: public (model-layout) signature -> pallas/ref impls
# ---------------------------------------------------------------------------

def _attention_ref_bshd(q, k, v, *, causal: bool = True):
    b, s, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qk = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kk = k.transpose(0, 2, 1, 3).reshape(b * hkv, k.shape[1], d)
    vk = v.transpose(0, 2, 1, 3).reshape(b * hkv, v.shape[1], d)
    o = attention_ref(qk, kk, vk, group=g, causal=causal)
    return o.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _attention_pallas(q, k, v, *, params: dict, causal: bool = True):
    return _fa_ops.flash_attention(q, k, v, causal=causal,
                                   block_q=params["block_q"],
                                   block_k=params["block_k"])


def _attention_accepts(params: dict, args, kwargs: dict) -> bool:
    """Non-causal attention on ragged tiles would fold padded keys into
    the softmax; causal masking already excludes the tail (kpos > qpos)."""
    q, k = args[0], args[1]
    return kwargs.get("causal", True) or not (
        q.shape[1] % params["block_q"] or k.shape[1] % params["block_k"])


def _always(params: dict, args, kwargs: dict) -> bool:
    return True


def _attention_space(q, k, v):
    s, sk = q.shape[1], k.shape[1]
    return _dedup([{"block_q": min(bq, s), "block_k": min(bk, sk)}
                   for bq in (64, 128) for bk in (64, 128)])


def _attention_normalize(params: dict, args) -> dict:
    q, k = args[0], args[1]
    return {"block_q": min(int(params["block_q"]), q.shape[1]),
            "block_k": min(int(params["block_k"]), k.shape[1])}


def _matmul_int8_pallas(x, w, scale, *, params: dict,
                        out_dtype=jnp.float32):
    return _im_ops.int8_matmul(x, w, scale, block_m=params["block_m"],
                               block_n=params["block_n"],
                               block_k=params["block_k"],
                               out_dtype=out_dtype)


def _matmul_int8_ref(x, w, scale, *, out_dtype=jnp.float32):
    return int8_matmul_ref(x, w, scale, out_dtype=out_dtype)


def _matmul_int8_space(x, w, scale):
    m, kdim = x.shape
    n = w.shape[1]
    return _dedup([{"block_m": _divisor_leq(m, blk),
                    "block_n": _divisor_leq(n, blk),
                    "block_k": _divisor_leq(kdim, blk)}
                   for blk in (64, 128, 256)])


def _matmul_int8_normalize(params: dict, args) -> dict:
    x, w = args[0], args[1]
    return {"block_m": _divisor_leq(x.shape[0], params["block_m"]),
            "block_n": _divisor_leq(w.shape[1], params["block_n"]),
            "block_k": _divisor_leq(x.shape[1], params["block_k"])}


def _ssm_scan_pallas(da, bx, c, *, params: dict):
    b, t, di, n = da.shape
    chunk = min(int(params["chunk"]), t)
    tp = -(-t // chunk) * chunk
    if tp != t:
        # identity padding (da=0 keeps h, bx=0 adds nothing); padded y
        # rows are sliced off below — ragged T rides the tiled kernel
        pad4 = [(0, 0), (0, tp - t), (0, 0), (0, 0)]
        da = jnp.pad(da, pad4)
        bx = jnp.pad(bx, pad4)
        c = jnp.pad(c, [(0, 0), (0, tp - t), (0, 0)])
    y = _ss_ops.ssm_scan(da, bx, c, chunk=chunk,
                         d_block=_divisor_leq(di, params["d_block"]))
    return y[:, :t]


def _ssm_scan_space(da, bx, c):
    t, di = da.shape[1], da.shape[2]
    return _dedup([{"chunk": min(ch, t), "d_block": _divisor_leq(di, db)}
                   for ch in (8, 16, 32) for db in (128, 256)])


def _ssm_scan_normalize(params: dict, args) -> dict:
    da = args[0]
    return {"chunk": min(int(params["chunk"]), da.shape[1]),
            "d_block": _divisor_leq(da.shape[2], params["d_block"])}


def _wkv6_pallas(r, k, v, lw, u, *, params: dict):
    b, t, h, kk = r.shape
    chunk = min(int(params["chunk"]), t)
    tp = -(-t // chunk) * chunk
    if tp != t:
        # identity padding: k=v=0 adds nothing to the state, lw=0 leaves
        # it undecayed, r=0 makes the padded y rows zeros (sliced off)
        pad4 = [(0, 0), (0, tp - t), (0, 0), (0, 0)]
        r, k, v, lw = (jnp.pad(a, pad4) for a in (r, k, v, lw))
    y = _wk_ops.wkv6(r, k, v, lw, u, chunk=chunk)
    return y[:, :t]


def _wkv6_ref_bthk(r, k, v, lw, u):
    b, t, h, kk = r.shape

    def fold(a):
        return a.transpose(0, 2, 1, 3).reshape(b * h, t, kk)

    uf = jnp.broadcast_to(u[None], (b, h, kk)).reshape(b * h, kk)
    y = wkv6_ref(fold(r), fold(k), fold(v), fold(lw), uf)
    return y.reshape(b, h, t, kk).transpose(0, 2, 1, 3)


def _wkv6_space(r, k, v, lw, u):
    t = r.shape[1]
    return _dedup([{"chunk": min(ch, t)} for ch in (16, 32, 64)])


def _wkv6_normalize(params: dict, args) -> dict:
    return {"chunk": min(int(params["chunk"]), args[0].shape[1])}


# Registry-level contracts re-use the ops.py checkers but relax the block
# tiling constraints (block_*=1 always tiles): the registry pads ragged
# sequences and normalizes block sizes itself, so only the semantic
# shape/dtype rules apply here.

def _contract_matmul_int8(x, w, scale):
    _im_ops.check_contract(x, w, scale, block_m=1, block_n=1, block_k=1)


def _contract_ssm_scan(da, bx, c):
    _ss_ops.check_contract(da, bx, c, chunk=1, d_block=1)


def _contract_wkv6(r, k, v, lw, u):
    _wk_ops.check_contract(r, k, v, lw, u, chunk=1)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One kernel: pallas impl + ref oracle + contract + autotune space."""
    name: str
    pallas: Callable                    # (*args, params=dict, **kw) -> out
    ref: Callable                       # (*args, **kw) -> out
    contract: Callable                  # (*args) -> None or ValueError
    space: Callable                     # (*args) -> list[dict] candidates
    normalize: Callable                 # (params, args) -> valid params
    defaults: tuple                     # ((param, value), ...)
    accepts: Callable = _always         # (params, args, kw) -> pallas ok?


class KernelRegistry:
    """Kernel specs + per-(shape, dtype, backend) autotuned block sizes."""

    def __init__(self):
        self.specs: dict[str, KernelSpec] = {}
        # signature -> {"params": dict, "us": float|None, "source": str}
        self.winners: dict[str, dict] = {}
        self.sweep_trials = 0           # timed candidate runs, ever
        self.stats: dict[str, int] = {}

    # ------------------------------------------------------------- plumbing
    def register(self, spec: KernelSpec) -> None:
        self.specs[spec.name] = spec

    def get(self, name: str) -> KernelSpec:
        spec = self.specs.get(name)
        if spec is None:
            raise KeyError(f"unknown kernel {name!r}; registered: "
                           f"{sorted(self.specs)}")
        return spec

    def _count(self, key: str, n: int = 1) -> None:
        self.stats[key] = self.stats.get(key, 0) + n

    def signature(self, name: str, args, kwargs: Optional[dict] = None) -> str:
        shapes = ";".join(
            f"{tuple(a.shape)}:{jnp.dtype(a.dtype)}" for a in args)
        extra = json.dumps({k: str(v) for k, v in (kwargs or {}).items()},
                           sort_keys=True)
        return f"{name}|{jax.default_backend()}|{shapes}|{extra}"

    # ------------------------------------------------------------- dispatch
    def params_for(self, name: str, args,
                   kwargs: Optional[dict] = None) -> dict:
        """Autotuned winner for this site, else normalized defaults."""
        spec = self.get(name)
        hit = self.winners.get(self.signature(name, args, kwargs))
        if hit is not None:
            self._count("params_hit")
            return dict(hit["params"])
        self._count("params_default")
        return spec.normalize(dict(spec.defaults), args)

    def call(self, name: str, *args, impl: Optional[str] = None,
             params: Optional[dict] = None, **kwargs):
        """Dispatch one kernel: explicit ``impl`` first, then pallas
        wherever the spec accepts the shapes, else the ref oracle."""
        spec = self.get(name)
        spec.contract(*args)
        if impl not in (None, "pallas", "ref"):
            raise ValueError(f"kernel {name!r}: unknown impl {impl!r} "
                             f"(expected 'pallas' or 'ref')")
        if impl != "ref":
            if params is None:
                params = self.params_for(name, args, kwargs)
            else:
                params = spec.normalize(dict(params), args)
            if spec.accepts(params, args, kwargs):
                self._count(f"{name}_pallas")
                return spec.pallas(*args, params=params, **kwargs)
        self._count(f"{name}_ref")
        return spec.ref(*args, **kwargs)

    # ------------------------------------------------------------- autotune
    def autotune(self, name: str, *args, **kwargs):
        """Sweep the candidate space for this call site; returns
        ``(winning params, timed trials run)``. A cached winner (including
        one loaded from a RIMFS image) costs zero trials."""
        spec = self.get(name)
        spec.contract(*args)
        key = self.signature(name, args, kwargs)
        hit = self.winners.get(key)
        if hit is not None:
            self._count("autotune_hit")
            return dict(hit["params"]), 0
        best, best_t = None, None
        trials = 0
        for cand in spec.space(*args):
            out = spec.pallas(*args, params=cand, **kwargs)
            jax.block_until_ready(out)             # compile + warm
            t0 = time.perf_counter()
            jax.block_until_ready(spec.pallas(*args, params=cand, **kwargs))
            dt = time.perf_counter() - t0
            trials += 1
            if best_t is None or dt < best_t:
                best, best_t = dict(cand), dt
        self.sweep_trials += trials
        self._count("autotune_sweep")
        self.winners[key] = {"params": best, "us": best_t * 1e6,
                             "source": "sweep"}
        return dict(best), trials

    # ----------------------------------------------------------- persistence
    def pack_image(self) -> bytes:
        """Serialize the winner table as a RIMFS image (one JSON file)."""
        payload = json.dumps({"version": 1, "winners": self.winners},
                             sort_keys=True).encode()
        return rimfs_mod.pack(
            {AUTOTUNE_FILE: np.frombuffer(payload, np.uint8)})

    def load_image(self, image) -> int:
        """Merge winners from a RIMFS image (bytes or mounted RIMFS).
        Returns the number of entries installed. Loaded entries satisfy
        ``autotune`` with zero sweep trials — the provision-time reload."""
        fs = rimfs_mod.mount(image) \
            if isinstance(image, (bytes, bytearray, memoryview)) else image
        data = json.loads(bytes(np.asarray(fs.read(AUTOTUNE_FILE))).decode())
        if data.get("version") != 1:
            raise ValueError(
                f"autotune image version {data.get('version')!r} != 1")
        n = 0
        for key, entry in data["winners"].items():
            if key not in self.winners:
                self.winners[key] = {"params": dict(entry["params"]),
                                     "us": entry.get("us"),
                                     "source": "loaded"}
                n += 1
        return n

    def reset(self) -> None:
        """Drop all winners and counters (a fresh provision)."""
        self.winners.clear()
        self.sweep_trials = 0
        self.stats.clear()


def _build_default_registry() -> KernelRegistry:
    reg = KernelRegistry()
    reg.register(KernelSpec(
        "attention",
        _attention_pallas,
        _attention_ref_bshd,
        _fa_ops.check_contract,
        _attention_space, _attention_normalize,
        (("block_q", 128), ("block_k", 128)),
        _attention_accepts))
    reg.register(KernelSpec(
        "matmul_int8",
        _matmul_int8_pallas,
        _matmul_int8_ref,
        _contract_matmul_int8,
        _matmul_int8_space, _matmul_int8_normalize,
        (("block_m", 128), ("block_n", 128), ("block_k", 128))))
    reg.register(KernelSpec(
        "ssm_scan",
        _ssm_scan_pallas,
        ssm_scan_ref,
        _contract_ssm_scan,
        _ssm_scan_space, _ssm_scan_normalize,
        (("chunk", 16), ("d_block", 256))))
    reg.register(KernelSpec(
        "wkv6",
        _wkv6_pallas,
        _wkv6_ref_bthk,
        _contract_wkv6,
        _wkv6_space, _wkv6_normalize,
        (("chunk", 64),)))
    return reg


REGISTRY = _build_default_registry()


# ---------------------------------------------------------------------------
# Module-level API (the singleton most call sites use)
# ---------------------------------------------------------------------------

def get(name: str) -> KernelSpec:
    return REGISTRY.get(name)


def call(name: str, *args, **kwargs):
    return REGISTRY.call(name, *args, **kwargs)


def autotune(name: str, *args, **kwargs):
    return REGISTRY.autotune(name, *args, **kwargs)


def params_for(name: str, args, kwargs: Optional[dict] = None) -> dict:
    return REGISTRY.params_for(name, args, kwargs)


def pack_image() -> bytes:
    return REGISTRY.pack_image()


def load_image(image) -> int:
    return REGISTRY.load_image(image)


def reset() -> None:
    REGISTRY.reset()


def call_op(name: str, srcs, attrs) -> Any:
    """Kernel-op entry used by core/oplib: unpack RCB attrs into the
    semantic keyword signature. Attrs must stay JSON-wire-safe."""
    attrs = attrs or {}
    impl = attrs.get("impl")
    params = attrs.get("params")
    if name == "attention":
        return call("attention", *srcs, impl=impl, params=params,
                    causal=bool(attrs.get("causal", True)))
    if name == "matmul_int8":
        return call("matmul_int8", *srcs, impl=impl, params=params,
                    out_dtype=jnp.dtype(attrs.get("out_dtype", "float32")))
    return call(name, *srcs, impl=impl, params=params)


def linked_handler(name: str, attrs) -> Callable:
    """Build the specialized positional handler ``fn(*srcs)`` the RHAL
    ``link_compute`` vtables hand to core/linker.py for kernel opcodes.
    Block-size lookup happens per call (shapes are only known then); the
    heavy math runs through the kernels' shared jitted wrappers, so eager
    linked dispatch and traced fusion hit the same executables."""
    def handler(*srcs):
        return call_op(name, srcs, attrs)
    return handler
