"""A run with its timed path broken underneath reads ``correct: false``.

Each fault the cells can have is planted in the plan the driver builds
(the harness's look for a chip is skipped; the driver, the engine and
the reference run as in a cell, at a tiny size):

- a fold that returns its state unchanged;
- half of every chunk left out (its edges marked invalid) before the codec;
- an answer altered where it is produced (one label of each emission).

No cell spans chips, so there is no exchange between chips to leave out.
"""

import jax.numpy as jnp
import pytest

from benchmark.drivers import common

from .tiny import run

CELLS = ["cc-twitter2010-file"]


def unchanged_state(agg):
    agg.fold_compressed = lambda s, payload: s


def half_the_batch(agg):
    compress = agg.host_compress

    def halved(chunk):
        valid = chunk.valid.copy()
        valid[valid.shape[0] // 2:] = False
        return compress(chunk._replace(valid=valid))

    agg.host_compress = halved


def altered_answer(agg):
    transform = agg.transform
    agg.transform = lambda s: transform(s).at[7].add(jnp.int32(1))


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    out = run(cell, 2**31 + 101)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("fault", [unchanged_state, half_the_batch,
                                   altered_answer])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_reads_not_correct(cell, fault, monkeypatch):
    build = common.build_plan

    def broken(config):
        agg = build(config)
        fault(agg)
        return agg

    monkeypatch.setattr(common, "build_plan", broken)
    out = run(cell, 2**31 + 202)
    assert not out["correct"], out["checks"]
    assert out["checks"]["label_mismatches"]["value"] > 0
    assert out["failed"] > 0
