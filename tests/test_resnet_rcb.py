"""ResNet-18 case study through the full AEG path (paper §3.3/§4.3)."""
import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.resnet18 import CONFIG
from repro.core import quant, rbl, rctc, rimfs
from repro.core.executor import Executor
from repro.core.rcb import Op, RCBProgram
from repro.models import resnet as rn


def _setup(rng, batch=4):
    cfg = CONFIG.smoke()
    params = rn.init_resnet(jax.random.PRNGKey(0), cfg)
    x = rng.rand(batch, cfg.image_size, cfg.image_size, 3) \
        .astype(np.float32)
    return cfg, params, x


def test_rcb_resnet_matches_oracle(rng):
    cfg, params, x = _setup(rng)
    folded = rn.fold_bn(params)
    prog, image = rctc.compile_resnet18(cfg, folded, batch=x.shape[0])
    prog = RCBProgram.decode(prog.encode())           # over the wire
    bound = rbl.bind(prog, rimfs=rimfs.mount(image), inputs={"input": x},
                     verify_weights=True)
    out = np.asarray(Executor().run(bound)["output"])
    ref = np.asarray(rn.resnet_forward(cfg, params, jnp.asarray(x)))
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_int8_resnet_agreement(rng):
    """INT8 deployment mechanism check. With an UNTRAINED net the logits are
    near-ties, so argmax agreement is a noisy metric (the paper's 0.22pt
    top-1 gap is on trained ImageNet weights); we require argmax agreement
    well above chance AND small probability drift."""
    cfg, params, x = _setup(rng, batch=32)
    folded = rn.fold_bn(params)
    pack = quant.quantize_resnet(cfg, folded, x[:4])
    prog_q, image_q = rctc.compile_resnet18(cfg, folded, batch=32,
                                            int8=pack)
    bound = rbl.bind(prog_q, rimfs=rimfs.mount(image_q),
                     inputs={"input": x})
    out_q = np.asarray(Executor().run(bound)["output"])
    ref = np.asarray(rn.resnet_forward(cfg, params, jnp.asarray(x)))
    assert bool(np.all(np.isfinite(out_q)))
    agree = quant.top1_agreement(ref, out_q)
    assert agree >= 0.6, agree                  # chance = 1/num_classes
    assert float(np.mean(np.abs(ref - out_q))) < 0.08


def test_fused_resnet_single_dispatch(rng):
    """Fused mode executes the whole network as ONE XLA program."""
    cfg, params, x = _setup(rng)
    folded = rn.fold_bn(params)
    prog, image = rctc.compile_resnet18(cfg, folded, batch=x.shape[0])
    bound = rbl.bind(prog, rimfs=rimfs.mount(image))
    ex = Executor()
    fused = ex.fuse(bound)
    out = np.asarray(fused({"input": x}, ex.weights_from(bound))["output"])
    ref = np.asarray(rn.resnet_forward(cfg, params, jnp.asarray(x)))
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_weights_image_size_tracks_params(rng):
    """Paper: 12.63 MB parameter buffer — our image overhead must be <1%."""
    cfg, params, x = _setup(rng)
    folded = rn.fold_bn(params)
    _, image = rctc.compile_resnet18(cfg, folded, batch=1)
    payload = sum(np.asarray(v).nbytes for v in folded.values())
    assert len(image) < payload * 1.02 + 4096


def test_published_widths_64px_runs_stem_maxpool(rng):
    """ResNet-18 at its published widths on a 64x64 input, batch 1: the
    smallest image for which the lowering emits the stem max-pool, so the
    RCB path runs the op sequence the served 224x224 model runs. The
    interpreted, linked and batched paths each match the oracle; the
    interpreted and linked paths match each other bit for bit."""
    from test_conformance import assert_batched_close
    cfg = dataclasses.replace(CONFIG, image_size=64)
    params = rn.init_resnet(jax.random.PRNGKey(0), cfg)
    prog, image = rctc.compile_resnet18(cfg, rn.fold_bn(params), batch=1)
    assert Op.MAXPOOL in {op.op for blk in prog.blocks for op in blk.ops}
    fs = rimfs.mount(image)
    xs = [rng.rand(1, 64, 64, 3).astype(np.float32) for _ in range(3)]
    ex = Executor()
    bound = rbl.bind(prog, rimfs=fs)
    batched = ex.run_batched(bound, [{"input": x} for x in xs], rimfs=fs)
    assert ex.batch_stats["batchable"]
    for x, got in zip(xs, batched):
        ref = np.asarray(rn.resnet_forward(cfg, params, jnp.asarray(x)))
        b = rbl.bind(prog, rimfs=fs, inputs={"input": x})
        interp = np.asarray(ex.run_interpreted(b)["output"])
        linked = np.asarray(ex.run(b)["output"])
        np.testing.assert_array_equal(interp, linked)
        np.testing.assert_allclose(linked, ref, atol=1e-5, rtol=1e-5)
        assert_batched_close(linked, got["output"])
