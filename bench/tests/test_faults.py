"""A run with its timed path broken underneath comes out not correct.

Each test skips only the harness's look for a chip: it drives the rest of a
run (server, load, window, drain, check) on the CPU at a small size, with
one fault planted in the program where the answer is produced. A sound run
at the same size comes out correct, so the check is what catches the
fault."""
import numpy as np
import pytest

import run as bench_run
from conftest import tiny_cell

SEED = 2 ** 31 + 77


def _run(name: str) -> dict:
    return bench_run.execute(tiny_cell(name), SEED, 2.0, trace=False)


@pytest.mark.parametrize("name", ["resnet18.steady", "resnet18.bulk",
                                  "qwen2-1.5b.chat"])
def test_sound_run_is_correct(name, fresh_programs):
    out = _run(name)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0
    assert list(out)[-1] == "checks"


def _swap_two_classes(out: dict) -> dict:
    y = np.array(out["output"])
    y[..., [0, 1]] = y[..., [1, 0]]
    return {**out, "output": y}


@pytest.mark.parametrize("name", ["resnet18.steady", "resnet18.bulk"])
def test_resnet_answer_altered(name, monkeypatch, fresh_programs):
    """Two classes swapped in every reply, on the solo linked path and on
    the coalesced batched path."""
    from repro.core.executor import Executor
    run, batched = Executor.run, Executor.run_batched
    monkeypatch.setattr(Executor, "run", lambda self, *a, **k:
                        _swap_two_classes(run(self, *a, **k)))
    monkeypatch.setattr(Executor, "run_batched", lambda self, *a, **k: [
        _swap_two_classes(o) for o in batched(self, *a, **k)])
    out = _run(name)
    assert not out["correct"]
    assert out["checks"]["logp_gap"]["value"] > \
        out["checks"]["logp_gap"]["limit"]


def test_lm_token_altered(monkeypatch, fresh_programs):
    """The decode program samples the runner-up for every token."""
    import jax
    import jax.numpy as jnp
    from repro.launch import steps

    def runner_up(logits, greedy, temperature, key=None):
        return jax.lax.top_k(logits, 2)[1][..., 1].astype(jnp.int32)

    monkeypatch.setattr(steps, "sample_tokens", runner_up)
    out = _run("qwen2-1.5b.chat")
    assert not out["correct"]


def test_lm_cache_write_left_out(monkeypatch, fresh_programs):
    """Decode steps leave the KV pool unchanged: the state a step should
    hand on is dropped, so later tokens attend to a stale cache."""
    from repro.models import attention
    real = attention.decode_attention_paged

    def stale(cfg, p, x, pos, pool_k, pool_v, tables):
        y, _, _ = real(cfg, p, x, pos, pool_k, pool_v, tables)
        return y, pool_k, pool_v

    monkeypatch.setattr(attention, "decode_attention_paged", stale)
    out = _run("qwen2-1.5b.chat")
    assert not out["correct"]
