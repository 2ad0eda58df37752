"""Closed-loop defense: suspicion-weighted GARs + rule escalation.

The counterpart of ``attacks/adaptive.py`` (DESIGN.md §16). The telemetry
plane already derives a per-rank suspicion score (exclusion frequency
under the active rule, ``telemetry/hub.py``); this module turns that
audit signal back into aggregation decisions, in ONE module deployed at
both scales — the ``utils/rounds.py`` pattern:

  - **Suspicion weighting** (``suspicion_weights``): a rank's rows enter
    the GAR scaled by ``max((1 - suspicion)^power, floor)``. The law is
    dual-backend (numpy on the host-plane PS, traced jnp for the
    in-graph emulation's carried suspicion EMA) and EXACTLY 1.0 at
    suspicion 0 — an all-clean history composes the unchanged stack, the
    defense-off bitwise contract's weighted half. The weights ride the
    SAME row-scale composition the bounded-staleness discount built
    (``fold.folded_tree_aggregate(row_weights=)`` on Gram rules, explicit
    row scaling elsewhere), so the folded-attack fast path survives.
  - **Escalation** (``EscalationPolicy``): a hysteresis state machine
    over an ordered ladder of rules — default ``krum`` (classic
    single-select) -> ``multi-krum`` (selective averaging) -> ``bulyan``
    (trimmed second phase, the strongest and costliest) — driven by the
    CONCENTRATION of suspicion (``suspicion_concentration``: how much
    the top-f ranks' suspicion exceeds the crowd's). Concentration above
    ``theta_up`` for ``patience`` consecutive rounds escalates one
    level; concentration below ``theta_down`` for ``clean_window``
    consecutive rounds de-escalates. ``theta_down < theta_up`` strictly,
    and a reading BETWEEN the thresholds resets both counters — a
    boundary-riding adversary cannot flap the rule (pinned in
    tests/test_defense.py).

Why both: an adaptive attacker that stays just under the exclusion
threshold (attacks/adaptive.py) is *selected*, so its suspicion stays
low — weighting alone cannot catch it. But its probing rounds and its
bursts ARE excluded, concentration rises, and escalation swaps in a rule
with a lower admission threshold (Bulyan's trimmed phase bounds exactly
the coordinate-wise excess the lie attack injects); the attacker's
bracket then re-closes at a smaller magnitude, and the accuracy bar is
restored (XLA:CPU, round 14).
"""

import dataclasses

import numpy as np

__all__ = [
    "DEFAULT_LEVELS",
    "LEVEL_RULES",
    "suspicion_weights",
    "suspicion_concentration",
    "EscalationConfig",
    "EscalationPolicy",
    "PlaneDefense",
    "DefensePlan",
    "resolve",
]

# The escalation ladder, weakest (cheapest) first. "krum" is the classic
# rule (select ONE best-scored gradient, m=1); "multi-krum" is this
# repo's krum default (average the m = n - f - 2 best); "bulyan" runs
# multi-krum phase 1 plus the coordinate-trimmed phase 2. Each level
# resolves to a registered rule + gar_params overlay via LEVEL_RULES.
DEFAULT_LEVELS = ("krum", "multi-krum", "bulyan")

LEVEL_RULES = {
    "krum": ("krum", {"m": 1}),
    "multi-krum": ("krum", {}),
    "bulyan": ("bulyan", {}),
    "median": ("median", {}),
    "tmean": ("tmean", {}),
    "cclip": ("cclip", {}),
}


def resolve_level(level):
    """(gar_name, gar_params) for an escalation-ladder level name."""
    if level not in LEVEL_RULES:
        raise ValueError(
            f"unknown escalation level {level!r}; available: "
            f"{sorted(LEVEL_RULES)}"
        )
    name, params = LEVEL_RULES[level]
    return name, dict(params)


def start_level(levels, gar_name, gar_params=None):
    """The ladder level an escalating defense STARTS at for a configured
    rule — matched by resolved SEMANTICS, never by name alone.

    The repo's default ``krum`` (m = n - f - 2) IS the ``multi-krum``
    level; starting the ladder at the name-matching ``krum`` level
    (classic, m = 1) would silently DOWNGRADE the deployed rule — and
    classic krum's single-select is categorically broken against a
    duplicate-cluster collusion fake (the f identical rows hand each
    other zero-distance neighbors, so one of them wins the score at
    essentially any magnitude; a floored adaptive-empire then stalls
    training from INSIDE the selection, DESIGN.md §17). An explicit
    ``gar_params {"m": 1}`` still starts at the classic level. Rules
    with no matching level start at 0 (the callers validate membership
    in LEVEL_RULES separately)."""
    m = dict(gar_params or {}).get("m")
    fallback = None
    for i, lv in enumerate(levels):
        name, params = resolve_level(lv)
        if name != gar_name:
            continue
        if params.get("m") == m:
            return i
        if fallback is None:
            fallback = i
    if fallback is not None:
        return fallback
    return 0


def suspicion_weights(suspicion, *, power=2.0, floor=0.1, relative=True):
    """Per-rank row weights from suspicion scores: ``max((1-s)^power,
    floor)`` with ``s`` the (by default RELATIVE) suspicion in [0, 1].

    ``relative=True`` (the default, and what the deployed defenses use)
    measures each rank's suspicion as its EXCESS over the crowd's
    median: ``s_rel = clip(s - median(s), 0, 1)``. Raw exclusion
    frequency is confounded under selective rules — krum at m of n
    refuses ``n - m`` rows EVERY round, and an adaptive cohort that
    stays admitted pushes its own exclusions onto honest ranks, so raw
    weighting would down-weight the victims and up-weight the attacker
    (the inversion hazard; DESIGN.md §16). Median-relative suspicion is
    translation-free: a uniformly-excluded crowd weighs 1.0, and only
    ranks refused MORE than their peers lose weight.

    Exactly 1.0 at relative suspicion 0 (``1.0 ** power`` is exact in
    IEEE), so a clean or uniform history leaves the stack bitwise
    unchanged through the row-scale composition — the same identity
    contract as ``rounds.staleness_weights`` at tau 0. ``floor > 0``
    keeps even a fully-suspect rank OBSERVABLE: a zero weight would
    freeze its suspicion (nothing left to select or exclude) and hand a
    rotation attack a permanent exit from the audit. Dual-backend like
    the staleness law: numpy in, numpy out (host PS); jnp/tracer in,
    jnp out (the in-graph carried-EMA emulation).
    """
    if not (0.0 <= floor <= 1.0):
        raise ValueError(f"floor must be in [0, 1], got {floor}")
    if power <= 0.0:
        raise ValueError(f"power must be > 0, got {power}")
    import jax
    import jax.numpy as jnp

    on_device = isinstance(suspicion, jax.Array)
    xp = jnp if on_device else np
    s = xp.clip(xp.asarray(suspicion, xp.float32), 0.0, 1.0)
    if relative:
        s = xp.clip(s - xp.median(s), 0.0, 1.0)
    w = xp.power(xp.float32(1.0) - s, xp.float32(power))
    return xp.maximum(w, xp.float32(floor)).astype(xp.float32)


def suspicion_concentration(suspicion, f):
    """How far the suspicion mass departs from uniform at the f-cohort
    scale: ``mean(top-f suspicion) - mean(bottom-f suspicion)``, in
    [0, 1]. ~0 for clean or uniformly noisy histories; high for BOTH
    Byzantine signatures — a pinned victim cohort (top-f -> 1 under a
    static attack the rule keeps refusing) AND a laundering/under-the-
    radar cohort (bottom-f conspicuously clean while the admitted fakes
    push the crowd's exclusions up, the adaptive-lie fingerprint). The
    escalation trigger. Works on numpy arrays/lists and jnp arrays
    (sort-based, no data-dependent shapes).
    """
    import jax
    import jax.numpy as jnp

    on_device = isinstance(suspicion, jax.Array)
    xp = jnp if on_device else np
    s = xp.clip(xp.asarray(suspicion, xp.float32), 0.0, 1.0)
    n = int(s.shape[0])
    f = int(f)
    if not (1 <= f < n):
        raise ValueError(f"need 1 <= f < n, got f={f}, n={n}")
    srt = xp.sort(s)  # ascending
    top = xp.mean(srt[n - f:])
    bottom = xp.mean(srt[:f])
    return (top - bottom).astype(xp.float32)


@dataclasses.dataclass(frozen=True)
class EscalationConfig:
    """Hysteresis parameters of the escalation state machine."""

    levels: tuple = DEFAULT_LEVELS
    theta_up: float = 0.5
    theta_down: float = 0.2
    patience: int = 3
    clean_window: int = 12

    def __post_init__(self):
        if len(self.levels) < 1:
            raise ValueError("need at least one escalation level")
        for lv in self.levels:
            resolve_level(lv)  # validates
        # A level change rebuilds the step around the SAME TrainState; a
        # ladder mixing stateful-center rules (cclip's carried v_0) with
        # stateless ones would change the state's structure mid-run.
        from . import gars

        stateful = {
            gars[resolve_level(lv)[0]].stateful_center for lv in self.levels
        }
        if len(stateful) > 1:
            raise ValueError(
                f"escalation ladder {self.levels} mixes stateful-center "
                "and stateless rules; the carried TrainState cannot "
                "change structure at a level transition"
            )
        if not (0.0 <= self.theta_down < self.theta_up):
            raise ValueError(
                "hysteresis needs 0 <= theta_down < theta_up, got "
                f"[{self.theta_down}, {self.theta_up}]"
            )
        if self.patience < 1 or self.clean_window < 1:
            raise ValueError("patience and clean_window must be >= 1")


class EscalationPolicy:
    """The defense's rule ladder: escalate under concentrated suspicion,
    de-escalate after a sustained clean window, never flap on a boundary.

    Counter semantics (the hysteresis contract, pinned in
    tests/test_defense.py): a concentration reading >= ``theta_up``
    increments the escalate counter and zeroes the clean counter; a
    reading <= ``theta_down`` does the reverse; a reading strictly
    BETWEEN the thresholds zeroes BOTH — sustained evidence on one side
    is required, so a value oscillating around either threshold (or
    parked between them) changes nothing. Every level change resets both
    counters: the new rule's steady state is measured, not the
    transient that triggered it (the cooldown idea of
    ``utils/autoscale.py``).
    """

    def __init__(self, config=None):
        self.config = config or EscalationConfig()
        self.level = 0
        self._hot = 0
        self._clean = 0
        self.escalations = 0
        self.deescalations = 0

    @property
    def level_name(self):
        return self.config.levels[self.level]

    def current(self):
        """(gar_name, gar_params) of the active level."""
        return resolve_level(self.level_name)

    def observe(self, concentration):
        """Fold one round's suspicion concentration; returns +1 on
        escalation, -1 on de-escalation, 0 otherwise."""
        c = float(concentration)
        cfg = self.config
        if c >= cfg.theta_up:
            self._hot += 1
            self._clean = 0
        elif c <= cfg.theta_down:
            self._clean += 1
            self._hot = 0
        else:
            # The hysteresis band: evidence for neither transition.
            self._hot = 0
            self._clean = 0
        if self._hot >= cfg.patience and self.level < len(cfg.levels) - 1:
            self.level += 1
            self._hot = 0
            self._clean = 0
            self.escalations += 1
            return 1
        if self._clean >= cfg.clean_window and self.level > 0:
            self.level -= 1
            self._hot = 0
            self._clean = 0
            self.deescalations += 1
            return -1
        return 0


class PlaneDefense:
    """Host-side closed-loop defense state for ONE aggregation plane.

    The SSMW PS derives its suspicion from its MetricsHub (it is the
    deployment's audit point); the other host planes — the MSMW replicas'
    gradient quorums, a LEARN node's gradient gather and model gossip —
    each see their own rank-attributed quorums and need their own
    independent history (DESIGN.md §17: "independent ladders per plane").
    One ``PlaneDefense`` carries, for one plane:

      - a decayed per-rank exclusion EMA (the MetricsHub windowed-
        suspicion law: ``obs``/``exc`` twins multiplied by
        ``0.5 ** (1/halflife)`` per fold — a rotation cannot launder it),
      - the ``suspicion_weights`` map (median-relative, floored), and
      - an optional per-plane ``EscalationPolicy`` whose ladder starts AT
        the plane's configured rule when that rule is a ladder level.

    ``fold(ranks, selected)`` ingests one round's audit: the quorum's
    rank ids plus the rule's per-row selection weights over exactly those
    rows (taps order). ``weights_for(ranks)`` returns the per-quorum-row
    weight vector (all-1.0 on a clean history — the caller dispatches
    the unweighted program then, preserving the bitwise contracts).
    ``observe()`` folds the current concentration into the ladder and
    returns the policy's action (0 when not escalating); the CALLER
    validates feasibility at its quorum size and calls ``revert`` on an
    infeasible level (the SSMW PS convention).
    """

    def __init__(self, plan, num_ranks, *, f, plane, base_gar,
                 base_params=None):
        self.plan = plan
        self.num_ranks = int(num_ranks)
        self.f = max(1, int(f))
        self.plane = str(plane)
        self.base_gar = base_gar
        self.base_params = dict(base_params or {})
        self._decay = 0.5 ** (1.0 / float(plan.halflife))
        self._obs = np.zeros(self.num_ranks, np.float64)
        self._exc = np.zeros(self.num_ranks, np.float64)
        self.policy = plan.policy()
        if self.policy is not None:
            levels = self.policy.config.levels
            if base_gar not in LEVEL_RULES:
                raise ValueError(
                    f"--defense escalate on the {self.plane!r} plane "
                    f"needs its rule to name an escalation-ladder level "
                    f"({sorted(LEVEL_RULES)}), got {base_gar!r}"
                )
            self.policy.level = start_level(
                levels, base_gar, self.base_params
            )

    def fold(self, ranks, selected):
        """One round's audit: ``ranks`` observed, ``selected`` the rule's
        per-row influence over exactly those rows."""
        ranks = np.asarray(ranks, np.int64)
        sel = np.asarray(selected, np.float64)
        obs_inc = np.zeros(self.num_ranks, np.float64)
        exc_inc = np.zeros(self.num_ranks, np.float64)
        np.add.at(obs_inc, ranks, 1.0)
        np.add.at(exc_inc, ranks, (sel <= 0.0).astype(np.float64))
        self._obs *= self._decay
        self._exc *= self._decay
        self._obs += obs_inc
        self._exc += exc_inc

    def suspicion(self):
        return self._exc / np.maximum(self._obs, 1e-9)

    def weights_full(self):
        """(num_ranks,) suspicion weights — exactly 1.0 pre-history."""
        return np.asarray(suspicion_weights(
            self.suspicion(), power=self.plan.power, floor=self.plan.floor
        ), np.float32)

    def weights_for(self, ranks):
        """Per-quorum-row weights for this round's rank composition, or
        None when every weight is exactly 1.0 (dispatch the unweighted
        program — the clean-history identity)."""
        w = self.weights_full()[np.asarray(ranks, np.int64)]
        if np.all(w == 1.0):
            return None
        return w.astype(np.float32)

    def concentration(self):
        return float(suspicion_concentration(self.suspicion(), self.f))

    def observe(self):
        """Fold this round's concentration into the per-plane ladder;
        returns the policy action (always 0 without escalation)."""
        if self.policy is None:
            return 0
        return self.policy.observe(self.concentration())

    def revert(self, action):
        """Undo an escalation the caller found infeasible at its quorum
        size (bulyan needs q >= 4f + 3)."""
        self.policy.level -= action

    def current(self):
        """(gar_name, gar_params) of the plane's active rule: the ladder
        level when escalating, else the configured base rule."""
        if self.policy is None:
            return self.base_gar, dict(self.base_params)
        name, lvl = resolve_level(self.policy.level_name)
        return name, {**self.base_params, **lvl}


@dataclasses.dataclass(frozen=True)
class DefensePlan:
    """Resolved ``--defense`` CLI intent (see ``resolve``)."""

    weighted: bool
    escalate: bool
    power: float = 2.0
    floor: float = 0.1
    halflife: float = 16.0
    escalation: EscalationConfig = None
    # Data-plane detectors (aggregators/dataplane.py, DESIGN.md §18):
    # the third plane of the closed loop — per-class head-gradient
    # fingerprints + spectral/2-means detection, their own EMA halflife.
    data: bool = False
    dp_tau: float = 2.0
    dp_power: float = 4.0
    dp_floor: float = 0.0
    dp_halflife: float = 8.0

    def policy(self):
        return EscalationPolicy(self.escalation) if self.escalate else None


# --defense mode table: (weighted, escalate, data). The GAR-side modes
# compose with the data plane via "+data" — the two defenses run
# SIMULTANEOUSLY (independent evidence, one row-weight algebra), which
# is how the backdoor bar is met (XLA:CPU, round 16) without giving up the
# adaptive-lie coverage the ladder provides.
DEFENSE_MODES = {
    "weighted": (True, False, False),
    "escalate": (True, True, False),
    "data": (False, False, True),
    "weighted+data": (True, False, True),
    "escalate+data": (True, True, True),
}


def resolve(args):
    """``DefensePlan`` from the CLI flags, or None when ``--defense`` is
    off. ``--defense weighted`` enables suspicion weighting alone;
    ``--defense escalate`` enables weighting AND the rule ladder (the
    full closed loop); ``--defense data`` enables the DATA-plane
    detectors alone (fingerprints + spectral/2-means — the only plane
    that sees a backdoor); ``weighted+data``/``escalate+data`` compose
    them. ``--defense_params`` tunes ``power``/``floor``/``halflife``
    (the suspicion EMA), the escalation knobs (``levels``/``theta_up``/
    ``theta_down``/``patience``/``clean_window``), and the data-plane
    knobs (``dp_tau``/``dp_power``/``dp_floor``/``dp_halflife``)."""
    mode = getattr(args, "defense", None)
    if not mode or mode == "none":
        return None
    if mode not in DEFENSE_MODES:
        raise SystemExit(
            f"unknown --defense mode {mode!r}; use one of "
            f"{sorted(DEFENSE_MODES)}"
        )
    weighted, escalate, data = DEFENSE_MODES[mode]
    p = dict(getattr(args, "defense_params", None) or {})
    esc = EscalationConfig(
        levels=tuple(p.pop("levels", DEFAULT_LEVELS)),
        theta_up=float(p.pop("theta_up", 0.5)),
        theta_down=float(p.pop("theta_down", 0.2)),
        patience=int(p.pop("patience", 3)),
        clean_window=int(p.pop("clean_window", 12)),
    )
    plan = DefensePlan(
        weighted=weighted,
        escalate=escalate,
        power=float(p.pop("power", 2.0)),
        floor=float(p.pop("floor", 0.1)),
        halflife=float(p.pop("halflife", 16.0)),
        escalation=esc,
        data=data,
        dp_tau=float(p.pop("dp_tau", 2.0)),
        dp_power=float(p.pop("dp_power", 4.0)),
        dp_floor=float(p.pop("dp_floor", 0.0)),
        dp_halflife=float(p.pop("dp_halflife", 8.0)),
    )
    if p:
        raise SystemExit(f"unknown --defense_params keys {sorted(p)}")
    return plan
