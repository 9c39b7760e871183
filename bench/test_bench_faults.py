"""A run with the timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip, plants one fault in the
program under test and drives the rest of a run at a small size: a step
that returns its state unchanged, half of each draw left out with the
mean taken over the rest, and an answer altered where it is produced.
The cell runs on one chip, so it has no exchange between chips to leave
out.
"""
from __future__ import annotations

import numpy as np

from bench.conftest import run_tiny, tiny_cell


def test_epoch_step_returning_its_state_unchanged(monkeypatch,
                                                  isolated_dirs):
    from repro.core import engine
    lane = engine._single_lane

    def stuck_lane(*args, **kwargs):
        ns = lane(*args, **kwargs)
        make_epoch = ns.make_epoch

        def make(*a, **k):
            run = make_epoch(*a, **k)
            return lambda state, ke: tuple(state) + tuple(run(state, ke))[6:]

        ns.make_epoch = make
        return ns

    monkeypatch.setattr(engine, "_single_lane", stuck_lane)
    result = run_tiny(tiny_cell("kron18.bc"))
    assert not result["correct"]
    assert result["checks"]["tau_gap"]["value"] > 0


def test_half_of_each_draw_left_out(monkeypatch, isolated_dirs):
    from repro.core import engine
    draw = engine.draw_fold

    def half(graph, key, n_samples, **kwargs):
        return draw(graph, key, max(1, n_samples // 2), **kwargs)

    monkeypatch.setattr(engine, "draw_fold", half)
    result = run_tiny(tiny_cell("kron18.bc"))
    assert not result["correct"]
    assert result["checks"]["tau_gap"]["value"] > 0


def test_answer_altered_where_it_is_produced(monkeypatch, isolated_dirs):
    from repro.core.estimators.kadabra import BetweennessEstimator
    finalize = BetweennessEstimator.finalize

    def altered(self, counts, tau, params, ctx):
        scores = np.array(finalize(self, counts, tau, params, ctx))
        top = int(np.argmax(scores))
        scores[top] = scores[top] * 1.5 + 0.3 / max(int(tau), 1)
        return scores

    monkeypatch.setattr(BetweennessEstimator, "finalize", altered)
    result = run_tiny(tiny_cell("kron18.bc"))
    assert not result["correct"]
    assert result["checks"]["bad_counts"]["value"] > 0
