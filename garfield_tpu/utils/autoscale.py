"""Load-driven worker autoscaling: the elastic half of the async plane.

The churn machinery (DESIGN.md §14) lets the cluster SURVIVE workers
appearing and disappearing — a killed worker's frames expire past the
staleness cutoff, a relaunched one rejoins through ``read_latest`` and
re-enters the admissible set. This module adds the other half (ROADMAP
item 3): PROVISIONING for load. A PS-side controller watches the round
telemetry it already produces — round wall time and the quorum's
admissibility margin — and decides when to spawn a fresh worker process
or retire a running one, so the deployment tracks a THROUGHPUT TARGET
instead of a fixed n.

Why round rate scales with the worker count in async mode: workers
publish-and-continue, so the bounded-staleness gather's binding
constraint in steady state is its freshness floor — at least one NEW
admissible frame per harvest (exchange.RoundCollector). W workers each
producing a gradient every T seconds supply W/T fresh frames per second,
so the PS's sustainable round rate is ~W/T: adding workers adds rate
linearly until the PS's own aggregate/update cost dominates. (The
synchronous plane has no such lever — its rate is pinned to the slowest
quorum member regardless of W, which is exactly why autoscaling composes
with ``--async`` and is refused without it.)

The control law is deliberately boring (hysteresis + cooldown, the
shape every production autoscaler converges to):

  - rate = window / sum(round_s over the last ``window`` rounds) — the
    MEAN-based throughput, deliberately not a median: async rounds
    complete in BURSTS (several workers' frames land together, a batch
    of harvests clears in microseconds, then a stall until the next
    batch), and a median over such a window reads the burst, not the
    throughput;
  - rate < target * up_margin  and active < max  ->  spawn one;
  - rate > target * down_margin and active > min and the quorum was
    never short an admissible frame all window      ->  retire one;
  - after any action, wait ``cooldown`` rounds with a CLEARED window so
    the new membership's steady state is measured, not the transient.

``target_rate <= 0`` auto-calibrates: the first full window's measured
rate becomes the target, so a deployment scaled for its initial load
holds that service level through load spikes without anyone computing
a number up front.

The mechanics of spawning/retiring live with the caller (apps/cluster.py
spawns real OS processes via ``worker_command``; the bench spawns follow
children): the controller only decides. Retirement is a CLEAN teardown,
not a kill: the PS sends the worker its stop sentinel (the worker exits
rc 0 through its normal end-of-run path), retires its exchange watchers
(``PeerExchange.remove_peer`` — the symmetric-teardown contract) and
drops it from the collector; a later spawn of the same rank rejoins
through the existing ``read_latest`` catch-up path and re-reads its own
data shard (re-admit = re-shard).
"""

import collections
import dataclasses
import sys

__all__ = [
    "AutoscaleConfig",
    "AutoscaleController",
    "worker_command",
]


@dataclasses.dataclass(frozen=True)
class AutoscaleConfig:
    """The deployment's elasticity contract.

    ``target_rate`` is rounds/s (<= 0 auto-calibrates from the first
    full window); ``min_workers``/``max_workers`` bound the active set
    (the min must keep the GAR feasible at q = min - f — the caller
    checks, it knows the rule); ``window`` rounds feed each decision and
    ``cooldown`` rounds separate consecutive actions.
    """

    target_rate: float = 0.0
    min_workers: int = 1
    max_workers: int = 1
    window: int = 8
    cooldown: int = 8
    up_margin: float = 0.9
    down_margin: float = 1.3

    def __post_init__(self):
        if self.min_workers < 1:
            raise ValueError(
                f"min_workers must be >= 1, got {self.min_workers}"
            )
        if self.max_workers < self.min_workers:
            raise ValueError(
                f"max_workers ({self.max_workers}) must be >= min_workers "
                f"({self.min_workers})"
            )
        if self.window < 1 or self.cooldown < 0:
            raise ValueError(
                f"window must be >= 1 and cooldown >= 0, got "
                f"({self.window}, {self.cooldown})"
            )
        if not 0 < self.up_margin <= 1.0 <= self.down_margin:
            raise ValueError(
                "margins must satisfy 0 < up_margin <= 1 <= down_margin, "
                f"got ({self.up_margin}, {self.down_margin})"
            )


class AutoscaleController:
    """Rolling-window rate controller; ``observe`` returns -1/0/+1.

    Host-side and allocation-free per round: one deque append and (on
    decision rounds) one median of ``window`` floats — nothing a
    sub-millisecond async round would notice.
    """

    def __init__(self, cfg):
        self.cfg = cfg
        self.target = float(cfg.target_rate)
        self._round_s = collections.deque(maxlen=cfg.window)
        self._margin_ok = collections.deque(maxlen=cfg.window)
        self._since_action = cfg.cooldown  # first decision needs no wait
        self.actions = 0
        self._pre_action = None  # rescind() snapshot (see _acted)

    def rate(self):
        """Mean throughput over the current window (rounds / total
        seconds — see the module docstring for why not a median), or
        None before the window fills (or right after an action clears
        it)."""
        if len(self._round_s) < self.cfg.window:
            return None
        total = sum(self._round_s)
        return (len(self._round_s) / total) if total > 0 else None

    def observe(self, round_s, *, active, quorum_margin=0):
        """Fold one round; returns +1 (spawn), -1 (retire) or 0.

        ``active`` is the current worker count, ``quorum_margin`` the
        gather's admissibility surplus (admissible frames minus q). A
        NEGATIVE margin anywhere in the window means the quorum already
        struggled (degrades/timeouts) — retiring into that would turn a
        wobble into an outage, so scale-down requires a clean window.
        """
        self._pre_action = None  # a rescind is only valid IMMEDIATELY
        self._round_s.append(float(round_s))
        self._margin_ok.append(quorum_margin >= 0)
        self._since_action += 1
        rate = self.rate()
        if rate is None:
            return 0
        if self.target <= 0:
            # Auto-calibration: the first full window IS the service
            # level this deployment signed up for.
            self.target = rate
            return 0
        if self._since_action <= self.cfg.cooldown:
            return 0
        if rate < self.target * self.cfg.up_margin:
            if active < self.cfg.max_workers:
                self._acted()
                return 1
            return 0
        if (rate > self.target * self.cfg.down_margin
                and active > self.cfg.min_workers
                and all(self._margin_ok)):
            self._acted()
            return -1
        return 0

    def _acted(self):
        self.actions += 1
        # Snapshot the pre-action accounting so a caller that cannot
        # actually perform the advised action (capacity, wire caps, no
        # standby) can rescind() it — a refused action must not consume
        # the cooldown window (the old behavior silenced the controller
        # for a full cooldown + window refill after doing NOTHING).
        self._pre_action = (
            list(self._round_s), list(self._margin_ok), self._since_action
        )
        self._since_action = 0
        # Measure the NEW membership's steady state, not the transient
        # (a spawning worker pays tens of seconds of jax boot; counting
        # those rounds would trigger a second spawn for the same cause).
        self._round_s.clear()
        self._margin_ok.clear()

    def rescind(self):
        """Undo the accounting of the action the LAST ``observe`` call
        advised — the caller refused it (fleet at its index capacity, a
        shard split past the wire header's 16-slot nibble, no standby
        to merge into). Restores the measurement window, the cooldown
        clock and the action count to their pre-advice state, so the
        refusal is accounting-free: the controller keeps measuring the
        UNCHANGED membership instead of a transient that never
        happened. Returns True if there was an action to rescind;
        becomes a no-op (False) once any later ``observe`` folds — at
        that point the window has moved on and a partial restore would
        splice two measurement regimes."""
        if self._pre_action is None:
            return False
        round_s, margin_ok, since = self._pre_action
        self._round_s.extend(round_s)
        self._margin_ok.extend(margin_ok)
        self._since_action = since
        self.actions -= 1
        self._pre_action = None
        return True


# CLI flags that configure the PS-side controller and must NOT leak into
# a spawned worker's command line (the worker would try to autoscale
# too). --task is re-written, not dropped.
_PS_ONLY_VALUED = (
    "--task", "--target_rate", "--autoscale_min", "--autoscale_max",
    "--autoscale_window", "--autoscale_cooldown",
)
_PS_ONLY_FLAGS = ("--autoscale",)


def worker_command(windex, argv=None, main_module=None, role="worker"):
    """This process's CLI, re-targeted at the ``{role}:windex`` role.

    The PS was launched as ``python -m garfield_tpu.apps.<app> --cluster
    ... --task ps:0 ...``; a spawned worker runs the SAME app with the
    same flags (dataset/model/gar/async must agree across roles — a
    disagreement is the wire codec's deployment-error path) minus the
    PS-only autoscale knobs, plus its own ``--task``. The module name
    comes from ``__main__.__spec__`` (set by ``-m`` execution); running
    the PS some other way must pass ``main_module`` explicitly.
    ``role`` generalizes the task name — the federated fleet spawns
    ``client:K`` drivers through the same derivation
    (federated/fleet.client_command).
    """
    if main_module is None:
        spec = getattr(sys.modules.get("__main__"), "__spec__", None)
        main_module = getattr(spec, "name", None)
        if main_module is None:
            raise RuntimeError(
                "cannot derive the worker command: the PS was not "
                "launched with `python -m <app>` (no __main__.__spec__); "
                "pass main_module explicitly"
            )
        if main_module.endswith(".__main__"):
            main_module = main_module[: -len(".__main__")]
    argv = list(sys.argv[1:]) if argv is None else list(argv)
    out = []
    i = 0
    while i < len(argv):
        a = argv[i]
        if a in _PS_ONLY_FLAGS or a.startswith(
            tuple(f + "=" for f in _PS_ONLY_VALUED)
        ):
            i += 1
            continue
        if a in _PS_ONLY_VALUED:
            i += 2
            continue
        out.append(a)
        i += 1
    return [sys.executable, "-m", main_module, *out,
            "--task", f"{role}:{int(windex)}"]
