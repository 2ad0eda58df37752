"""Load-driven autoscale controller (utils/autoscale.py, DESIGN.md §15).

Pure host-side unit coverage: the hysteresis + cooldown control law, the
mean-based (burst-proof) rate estimator, auto-calibration, the
quorum-margin scale-down gate, config validation, and the PS-argv ->
worker-argv command derivation. The multi-process e2e (a PS actually
spawning/retiring worker processes) lives in tests/test_async_cluster.py
(slow).
"""

import sys

import pytest

from garfield_tpu.utils import autoscale


def _cfg(**kw):
    base = dict(target_rate=10.0, min_workers=2, max_workers=8,
                window=4, cooldown=2)
    base.update(kw)
    return autoscale.AutoscaleConfig(**base)


def _feed(ctl, round_s, k, active, margin=0):
    """Feed k identical rounds; return the list of non-zero actions."""
    actions = []
    for _ in range(k):
        a = ctl.observe(round_s, active=active, quorum_margin=margin)
        if a:
            actions.append(a)
    return actions


class TestConfig:
    def test_validation(self):
        with pytest.raises(ValueError):
            _cfg(min_workers=0)
        with pytest.raises(ValueError):
            _cfg(max_workers=1)  # < min_workers=2
        with pytest.raises(ValueError):
            _cfg(window=0)
        with pytest.raises(ValueError):
            _cfg(up_margin=1.2)
        with pytest.raises(ValueError):
            _cfg(down_margin=0.9)
        _cfg()  # valid baseline


class TestController:
    def test_no_decision_before_window_fills(self):
        ctl = autoscale.AutoscaleController(_cfg())
        assert _feed(ctl, 1.0, 3, active=4) == []  # window=4: 3 < 4
        assert ctl.rate() is None

    def test_rate_is_mean_not_median(self):
        # Bursty rounds: three ~instant harvests then one long stall.
        # Median would read ~1000/s; the throughput is 4 rounds / 1.003 s.
        # active at max so the low rate cannot trigger a spawn (which
        # would clear the window under measurement).
        ctl = autoscale.AutoscaleController(_cfg())
        for r in (0.001, 0.001, 0.001, 1.0):
            ctl.observe(r, active=8)
        assert ctl.rate() == pytest.approx(4 / 1.003, rel=1e-6)

    def test_scale_up_below_target(self):
        ctl = autoscale.AutoscaleController(_cfg())
        # rate = 5/s < 10 * 0.9 -> spawn exactly once the window fills.
        assert _feed(ctl, 0.2, 4, active=4) == [1]
        # The action cleared the window: the next decision waits for a
        # full window of the NEW membership (3 more rounds: nothing).
        assert ctl.rate() is None
        assert _feed(ctl, 0.2, 3, active=5) == []
        # 4th post-action round: window full again, cooldown (2) passed.
        assert _feed(ctl, 0.2, 1, active=5) == [1]

    def test_scale_up_capped_at_max(self):
        ctl = autoscale.AutoscaleController(_cfg())
        assert _feed(ctl, 0.2, 8, active=8) == []  # already at max

    def test_scale_down_above_target_with_clean_margin(self):
        ctl = autoscale.AutoscaleController(_cfg())
        # rate = 20/s > 10 * 1.3, margin clean -> retire.
        assert _feed(ctl, 0.05, 6, active=6, margin=1) == [-1]

    def test_scale_down_blocked_by_struggling_quorum(self):
        ctl = autoscale.AutoscaleController(_cfg())
        # Same rate, but one round in the window was SHORT an admissible
        # frame (negative margin): retiring into that is forbidden.
        for j in range(8):
            a = ctl.observe(
                0.05, active=6, quorum_margin=(-1 if j == 5 else 1)
            )
            assert a <= 0
            if j >= 5:
                assert a == 0

    def test_scale_down_floored_at_min(self):
        ctl = autoscale.AutoscaleController(_cfg())
        assert _feed(ctl, 0.05, 8, active=2, margin=1) == []

    def test_in_band_rate_holds(self):
        ctl = autoscale.AutoscaleController(_cfg())
        # 10/s is inside [0.9, 1.3] x target: no action, ever.
        assert _feed(ctl, 0.1, 20, active=4, margin=1) == []

    def test_auto_calibration_locks_first_window(self):
        ctl = autoscale.AutoscaleController(_cfg(target_rate=0.0))
        _feed(ctl, 0.04, 4, active=4)  # first full window: 25/s
        assert ctl.target == pytest.approx(25.0)
        # A later slowdown is measured AGAINST that service level: one
        # slow round drags the 4-round mean under 0.9 x 25 already.
        assert _feed(ctl, 0.2, 4, active=4) == [1]


class TestWorkerCommand:
    def test_rewrites_task_and_strips_ps_only_flags(self):
        argv = [
            "--cluster", "cfg.json", "--task", "ps:0", "--async",
            "--autoscale", "--target_rate", "12.5", "--autoscale_min",
            "2", "--autoscale_max=6", "--gar", "median", "--fw", "1",
        ]
        cmd = autoscale.worker_command(
            3, argv=argv, main_module="garfield_tpu.apps.aggregathor"
        )
        assert cmd[:3] == [
            sys.executable, "-m", "garfield_tpu.apps.aggregathor"
        ]
        rest = cmd[3:]
        assert rest[-2:] == ["--task", "worker:3"]
        assert "--autoscale" not in rest
        assert "--target_rate" not in rest
        assert "--autoscale_min" not in rest
        assert not any(a.startswith("--autoscale_max") for a in rest)
        assert "ps:0" not in rest
        # Deployment-shape flags the worker MUST share survive.
        for keep in ("--cluster", "cfg.json", "--async", "--gar",
                     "median", "--fw", "1"):
            assert keep in rest

    def test_requires_module_spec(self, monkeypatch):
        # A PS not launched via `python -m <app>` has no __main__ spec
        # to derive the worker command from — fail loudly, don't guess.
        monkeypatch.setattr(sys.modules["__main__"], "__spec__", None,
                            raising=False)
        with pytest.raises(RuntimeError, match="main_module"):
            autoscale.worker_command(0, argv=[])

    def test_main_dunder_suffix_stripped(self):
        cmd = autoscale.worker_command(
            1, argv=[], main_module="garfield_tpu.apps.learn"
        )
        assert cmd[2] == "garfield_tpu.apps.learn"


class TestRescind:
    """A refused action (capacity, wire caps, no standby) must be
    accounting-free: rescind() restores the measurement window, the
    cooldown clock and the action count — but only IMMEDIATELY after
    the advising observe, before the window moves on."""

    def test_rescind_restores_window_cooldown_and_count(self):
        ctl = autoscale.AutoscaleController(_cfg())
        # Slow rounds: the 4th observe fills the window and advises
        # a spawn (one more observe would expire the rescind snapshot).
        assert _feed(ctl, 1.0, 4, active=4) == [1]
        assert ctl.actions == 1 and ctl._since_action == 0
        assert ctl.rescind() is True
        assert ctl.actions == 0
        assert ctl.rate() is not None  # window NOT cleared by a refusal
        # The controller keeps advising on the unchanged membership:
        # the very next observe can act again (no consumed cooldown).
        assert ctl.observe(1.0, active=4, quorum_margin=0) == 1

    def test_rescind_without_action_is_noop(self):
        ctl = autoscale.AutoscaleController(_cfg())
        assert ctl.rescind() is False
        _feed(ctl, 1.0, 3, active=4)  # window not yet full: no action
        assert ctl.rescind() is False
        assert ctl.actions == 0

    def test_rescind_expires_after_any_later_observe(self):
        ctl = autoscale.AutoscaleController(_cfg())
        assert _feed(ctl, 1.0, 4, active=4) == [1]
        ctl.observe(1.0, active=5, quorum_margin=0)  # window moved on
        assert ctl.rescind() is False
        assert ctl.actions == 1  # the unrescinded action stands
