"""``correct`` on the CPU: a sound run passes, the control and each fault
fail.  Drives ``run.run`` on the 6x6 cell with the chip check skipped.

    python -m pytest -q bench/tests/test_correct.py

The faults are those the cells can have: a re-plan that hands back the
previous plan unchanged (state left unchanged), half of each candidate
batch left out of the device search, and an answer altered where it is
produced (a reported latency, a chiplet of a plan).  No cell spans chips,
so there is no exchange between chips to leave out.
"""
import dataclasses

import pytest

import control
import run
import sut

CELL = "dc4_mesh6_cong.replan"
ARGS = ["--workload", CELL, "--seed", "2147483659", "--seconds", "1",
        "--trace", "0"]


def _run(factory=None):
    return run.run(ARGS, planner_factory=factory, require_tpu=False)


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["checks"]["plan_mismatch"]["value"] == 0
    assert res["checks"]["metric_rel_gap"]["value"] == 0.0
    assert list(res)[-1] == "checks"


def test_float32_control_is_not_correct():
    res = _run(control.Float32Planner)
    assert not res["correct"]
    gap = res["checks"]["metric_rel_gap"]
    assert gap["value"] > 100 * gap["limit"]


class _Stale(sut.Planner):
    """Returns the first re-plan it made for every later request."""

    def plan(self, anchors):
        if not hasattr(self, "_first"):
            self._first = super().plan(anchors)
        return self._first


class _Altered(sut.Planner):
    """Scales the reported latency of the first window by 1 + 1e-9."""

    def plan(self, anchors):
        out = super().plan(anchors)
        wr = out.windows[0]
        wr.result = dataclasses.replace(
            wr.result, latency=wr.result.latency * (1 + 1e-9))
        return out


class _Moved(sut.Planner):
    """Moves the last segment of window 0's first model to a free chiplet."""

    def plan(self, anchors):
        out = super().plan(anchors)
        wr = out.windows[0]
        used = {c for p in wr.plan.plans for c in p.chiplets}
        free = min(c for c in range(self.mcm.n_chiplets) if c not in used)
        p0 = wr.plan.plans[0]
        moved = dataclasses.replace(p0, chiplets=p0.chiplets[:-1] + (free,))
        wr.plan = dataclasses.replace(
            wr.plan, plans=(moved,) + wr.plan.plans[1:])
        return out


def _factory(cls):
    def make(config):
        p = sut.build(config)
        return cls(scenario=p.scenario, mcm=p.mcm, cfg=p.cfg)
    return make


@pytest.mark.parametrize("cls", [_Stale, _Altered, _Moved])
def test_faulty_planner_is_not_correct(cls):
    res = _run(_factory(cls))
    assert not res["correct"], res["checks"]


def test_half_the_batch_left_out_is_not_correct(monkeypatch):
    import repro.core.sched as sched

    real = sched.assemble_candidates

    def half(*a, **kw):
        """Every other candidate row of each batch, as constructed."""
        cand, tiers, (words, chips, seg_arr) = real(*a, **kw)
        cand = dataclasses.replace(
            cand, seg_id=cand.seg_id[::2], chiplets=cand.chiplets[::2],
            n_segs=cand.n_segs[::2],
            seg_ends=None if cand.seg_ends is None else cand.seg_ends[::2])
        return cand, tiers[::2], (words[::2], chips[::2], seg_arr[::2])

    monkeypatch.setattr(sched, "assemble_candidates", half)
    res = _run()
    assert not res["correct"], res["checks"]
    assert res["checks"]["plan_mismatch"]["value"] > 0


class _Compiling(sut.Planner):
    """Builds one more XLA program at the first request after set-up."""

    def plan(self, anchors):
        import jax
        import jax.numpy as jnp

        self._n = getattr(self, "_n", 0) + 1
        if self._n == self._warm + 1:
            jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
        return super().plan(anchors)


def test_a_compile_inside_the_window_is_not_correct():
    import loadgen

    mix = loadgen.load_mix("replan_hetcross6")

    def make(config):
        p = _factory(_Compiling)(config)
        p._warm = len(loadgen.distinct(mix, 4, 36))
        return p

    res = _run(make)
    assert not res["correct"], res["checks"]
    assert res["checks"]["compiles_in_window"]["value"] >= 1
    assert res["checks"]["plan_mismatch"]["value"] == 0
