"""Partial participation: seeded per-round cohorts with a priced f budget.

At 10^6+ clients no round ingests everyone — each round samples a cohort
and aggregates only it (the Bonawitz-style FL round structure). The
robustness consequence is the point (Baruch et al., arXiv:1902.06156):
variance-exploiting attacks get exactly as much headroom as the COHORT's
f/n ratio allows, so the Byzantine budget must be priced PER SAMPLED
COHORT, not globally — a global f declared against the population says
nothing about the round the adversary actually concentrates into.

Pricing: with a Byzantine population fraction ``p = byz_frac``, a
uniformly sampled cohort of ``c`` clients contains a hypergeometric
number of Byzantine members with mean ``c·p``; the budget charges the
mean plus ``slack_sigmas`` binomial standard deviations (the binomial
upper-bounds the hypergeometric variance), clamped into the hierarchy's
composed capacity (``aggregators.hierarchy.max_tolerated_f``). A cohort
whose priced budget exceeds what the configured hierarchy can compose is
REFUSED loudly at planning time — under-declaring f silently is exactly
the failure mode the robustness matrix tests document (budget exceeded
=> the aggregate may leave the tolerance envelope; tests/test_federated
pins both sides).

Sampling is seeded and deterministic in ``(seed, round)`` — every shard
process derives the SAME cohort without coordination (the sampler is
metadata, not state), and a fed_bench row is reproducible.
Client identity is the STABLE GLOBAL id, never the per-round cohort
index: suspicion keyed by cohort position would reset every round, which
is a free laundering channel for any resampled Byzantine client
(telemetry/hub.py keys its decayed client suspicion by these ids; the
rotation regression test pins it).

Stragglers across round boundaries compose with the bounded-staleness
policy of ``utils/rounds.py``: a sampled client that delivers a gradient
computed against an older round's model enters the cohort at weight
``decay**tau`` (``cohort_weights``), and past the hard cutoff it is
EXCLUDED from the round before the hierarchy is planned — a zero-weight
row must never reach a Gram rule, where an all-zero vector reads as a
perfectly central inlier (the same inversion DESIGN.md §18 documents for
toward-zero row scaling; recorded in §19 as a negative result, not
hidden).
"""

import math

import numpy as np

from ..aggregators import hierarchy
from ..utils import rounds as rounds_lib

__all__ = ["CohortSampler"]


class CohortSampler:
    """Seeded per-round client sampler with a per-cohort f budget."""

    def __init__(self, population, cohort_size, *, seed=0, byz_frac=0.0,
                 bucket_gar="krum", top_gar=None, bucket_size=None,
                 levels="auto", slack_sigmas=4.0, staleness=None):
        self.population = int(population)
        self.cohort_size = int(cohort_size)
        if not 1 <= self.cohort_size <= self.population:
            raise ValueError(
                f"cohort_size must be in [1, population={self.population}],"
                f" got {cohort_size}"
            )
        self.seed = int(seed)
        self.byz_frac = float(byz_frac)
        if not 0.0 <= self.byz_frac < 0.5:
            raise ValueError(
                f"byz_frac must be in [0, 0.5), got {byz_frac}"
            )
        self.slack_sigmas = float(slack_sigmas)
        self.staleness = staleness  # a rounds_lib.StalenessPolicy or None
        self._gar_cfg = dict(
            bucket_gar=bucket_gar, top_gar=top_gar, bucket_size=bucket_size,
            levels=levels,
        )

    # -- sampling -----------------------------------------------------------

    def cohort(self, round_):
        """Global client ids sampled for ``round_`` — deterministic in
        (seed, round), without replacement, in sampled order (arrival
        order maps cohort position -> hierarchy bucket, so the order is
        part of the seeded contract)."""
        rng = np.random.default_rng([self.seed, int(round_)])
        if self.cohort_size == self.population:
            # Full participation keeps the identity order: the S=1
            # full-participation trajectory must be bitwise the
            # unsharded path's, including bucket assignment.
            return np.arange(self.population, dtype=np.int64)
        return rng.choice(
            self.population, self.cohort_size, replace=False
        ).astype(np.int64)

    # -- f pricing ----------------------------------------------------------

    def capacity(self, c=None):
        """Largest f the configured hierarchy composes for a ``c``-member
        cohort (None when even f=0 is impossible)."""
        c = self.cohort_size if c is None else int(c)
        return hierarchy.max_tolerated_f(c, **self._gar_cfg)

    def f_budget(self, c=None):
        """The cohort's priced Byzantine budget: mean + slack·sigma of
        the sampled Byzantine count, clamped to >= 1 whenever the
        population carries any Byzantine mass (a tail can always land
        one). Raises ValueError when the price exceeds the hierarchy's
        composed capacity — the cohort is unaggregatable at the declared
        threat level and refusing loudly beats aggregating unsoundly."""
        c = self.cohort_size if c is None else int(c)
        p = self.byz_frac
        if p == 0.0:
            return 0
        mean = c * p
        sigma = math.sqrt(c * p * (1.0 - p))
        budget = max(1, int(math.ceil(mean + self.slack_sigmas * sigma)))
        cap = self.capacity(c)
        if cap is None or budget > cap:
            raise ValueError(
                f"cohort f budget {budget} (c={c}, byz_frac={p}, "
                f"{self.slack_sigmas} sigmas) exceeds the hierarchy's "
                f"composed capacity {cap} — shrink byz_frac, grow the "
                "cohort, or pick a stronger bucket/top rule"
            )
        return budget

    def realized_byzantine(self, cohort_ids, byz_ids):
        """How many of ``byz_ids`` (global ids) the cohort sampled — the
        simulation/audit-side ground truth the budget is checked against
        in fed_bench rows and the composition tests."""
        return int(np.isin(
            np.asarray(cohort_ids), np.asarray(list(byz_ids))
        ).sum())

    # -- staleness composition ----------------------------------------------

    def cohort_weights(self, round_, cohort_ids, tags=None):
        """(active_ids, weights, dropped_ids): the staleness-composed
        round membership. ``tags`` maps client id -> the round whose
        model its gradient used (missing/None = fresh). Weights follow
        ``utils.rounds.staleness_weights`` (exactly 1.0 when fresh);
        members past the hard cutoff are DROPPED from the round entirely
        — never passed as zero-weight rows (see the module docstring) —
        and the caller prices f on the ACTIVE count."""
        cohort_ids = np.asarray(cohort_ids, np.int64)
        if not tags or self.staleness is None:
            return cohort_ids, np.ones(cohort_ids.size, np.float32), \
                np.empty(0, np.int64)
        tau = np.zeros(cohort_ids.size, np.int64)
        for i, cid in enumerate(cohort_ids.tolist()):
            tag = tags.get(cid)
            if tag is not None:
                tau[i] = max(0, int(round_) - int(tag))
        w = self.staleness.weights(tau)
        keep = w > 0.0
        return cohort_ids[keep], np.asarray(w[keep], np.float32), \
            cohort_ids[~keep]
