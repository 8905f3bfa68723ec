"""A whole run of the harness, with the look for a chip skipped, sees
`correct` come out false when the timed path is broken underneath: a
tick that returns its state unchanged, half of the seed batch left out
(its place taken by the mean of the rest), and one answer altered, or
made NaN, where it is produced. The cells have no exchange between chips to leave out:
`devices=` splits the seed axis and the shards never communicate."""
import time

import jax
import numpy as np
import pytest

from bench.harness import driver
from bench.tests import tiny


def _unchanged(state, final, ys):
    n_cfg = np.asarray(final.emitted).shape[0]
    fin = type(final)(*(np.broadcast_to(np.asarray(s),
                                        (n_cfg,) + np.shape(s))
                        for s in state))
    return fin, {k: np.zeros_like(np.asarray(v)) for k, v in ys.items()}


def _half(state, final, ys):
    def h(a):
        a = np.array(a)
        k = a.shape[1] // 2
        a[:, k:] = np.mean(a[:, :k], axis=1, keepdims=True).astype(a.dtype)
        return a
    return type(final)(*(h(x) for x in final)), {k: h(v)
                                                 for k, v in ys.items()}


def _altered(state, final, ys):
    emitted = np.array(final.emitted)
    emitted[0, 0] *= 1.0 + 1e-6
    return final._replace(emitted=emitted), ys


def _nan(state, final, ys):
    emitted = np.array(final.emitted)
    emitted[0, 0] = np.nan
    return final._replace(emitted=emitted), ys


FAULTS = {"sound": None, "state_unchanged": _unchanged,
          "half_batch": _half, "answer_altered": _altered,
          "answer_nan": _nan}


@pytest.mark.parametrize("name", ["drill_fleet.gate_open",
                                  "q12_fleet.replication"])
@pytest.mark.parametrize("fault", list(FAULTS))
def test_broken_timed_path_is_not_correct(monkeypatch, fault, name):
    from repro.streams import jax_engine

    monkeypatch.setattr(driver, "chips", lambda n: jax.devices())
    alter = FAULTS[fault]
    if alter is not None:
        real = jax_engine.get_cached_config_fn

        def broken(desc, shared_kills=False):
            fn = real(desc, shared_kills)
            return lambda pa, state, xs: alter(state, *fn(pa, state, xs))

        monkeypatch.setattr(jax_engine, "get_cached_config_fn", broken)
    cell = tiny.cell(name, kill_prob=0.01)
    out, run = driver.run(cell, 2**31 + 5, 2.0, False, time.perf_counter())
    assert run.chunks_in_window()
    assert out["correct"] is (fault == "sound"), out["table"]
