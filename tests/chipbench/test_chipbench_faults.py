"""Faults planted in the timed path underneath a whole run of the harness
on the CPU at a tiny size: each has to make ``correct`` come out false."""
import json
import os
import sys

import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "chipbench"))

import run  # noqa: E402

DATA = os.path.join(HERE, "data")
MASK_ID = 255


def _select(kind):
    """The tick's top-k commit, broken: the least confident masked
    positions, or every masked position of the block at once."""
    from repro.core import sampling
    orig = sampling.topk_transfer_mask

    def topk(conf, mask_idx, k, use_kernel=None):
        if kind == "lowest_confidence":
            return orig(-conf, mask_idx, k, use_kernel)
        return orig(conf, mask_idx, jnp.where(k > 0, conf.shape[1], 0),
                    use_kernel)
    return topk


def _fault(kind, mask_id):
    from repro.core import diffusion
    orig = diffusion.get_tick_fn

    def get(*a, **kw):
        if kind in ("lowest_confidence", "whole_block"):
            # a fresh trace of the tick, with the commit broken inside it
            return orig.__wrapped__(*a, **kw)
        fn = orig(*a, **kw)

        def tick(params, x, kv_valid, bs, k, srng, cache=None):
            out = fn(params, x, kv_valid, bs, k, srng, cache)
            if kind == "unchanged":
                return (x, cache) + tuple(out[2:])
            if kind == "token":
                new = jnp.where(out[0] != x, (out[0] + 1) % mask_id, out[0])
                return (new,) + tuple(out[1:])
            # half the batch left out: its rows are served the other
            # half's results
            h = x.shape[0] // 2
            other = fn(params, jnp.concatenate([x[:h], x[:h]]), kv_valid,
                       bs, k, srng, cache)[0]
            new = jnp.concatenate([out[0][:h], jnp.where(
                x[h:] == mask_id, other[h:], x[h:])])
            return (new,) + tuple(out[1:])
        return tick
    return get


@pytest.mark.parametrize("kind", ["unchanged", "token", "half_batch",
                                  "lowest_confidence", "whole_block"])
def test_a_broken_timed_path_is_not_correct(kind, capsys, monkeypatch):
    from repro.core import diffusion, sampling
    monkeypatch.setattr(diffusion, "get_tick_fn", _fault(kind, MASK_ID))
    if kind in ("lowest_confidence", "whole_block"):
        monkeypatch.setattr(sampling, "topk_transfer_mask", _select(kind))
    assert run.run_cell("tiny-closed", 7, 3.0, False,
                        bench_path=os.path.join(DATA, "BENCHMARK.json"),
                        root=DATA, need_tpu=False) == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is False, result["check"]
