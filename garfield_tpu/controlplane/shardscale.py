"""Shard autoscaling: the elastic axis the worker autoscaler can't reach.

``utils.autoscale.AutoscaleController`` provisions WORKERS — more
gradient producers per second for the async plane. This module points
the same control law (mean-rate window, hysteresis, cooldown — and now
``rescind``) at the OTHER capacity axis: the PS shard count. Round
time scaled ~1/S (XLA:CPU, round 17) because every shard folds only d/S of
each client, so under round-latency pressure the right move is a span
SPLIT (S -> S+1, each shard thinner), and under sustained headroom a
MERGE (S -> S-1, fewer processes doing the same work). The controller
decides; ``FedRoundEngine.resize`` applies — re-plan the balanced
partition, rebuild the shard servers, and bump the membership epoch by
exactly one, so every split/merge is a membership change the wire
plane enforces (a client still slicing for the OLD spans sends frames
stamped with the old epoch: attributable rejects, not silently
mis-sliced folds — DESIGN.md §22).

Why the worker controller transplants cleanly: its inputs are
role-free. ``observe(round_s, active, quorum_margin)`` reads wall time
per round, a capacity count, and a health bit; here ``active`` is the
shard count and the health bit is "no shard's reducer was starved".
+1 (the controller's "spawn") means "add capacity" on either axis. The
one genuinely new case is REFUSAL: a split can be impossible (the wire
header's 16-slot shard nibble, or more shards than parameters) in a
way worker spawns never were, and the satellite-2 fix exists for
exactly this call site — a refused resize rescinds the controller
action, so the refusal costs nothing: no consumed cooldown, no cleared
measurement window, no phantom action count.
"""

from ..federated import sharding
from ..utils import autoscale

__all__ = ["ShardAutoscaler"]


class ShardAutoscaler:
    """Round-latency-driven split/merge of an engine's shard group.

    Call ``observe(round_s)`` once per finished round, BETWEEN rounds
    (``FedRoundEngine.resize`` rebuilds the shard servers, so applying
    mid-round would drop the round in flight). Returns the applied
    delta: +1 split, -1 merge, 0 nothing — refused actions are
    rescinded and return 0, indistinguishable from no advice because
    accounting-wise they ARE no advice.
    """

    def __init__(self, engine, *, target_rate=0.0, min_shards=1,
                 max_shards=None, window=8, cooldown=8,
                 up_margin=0.9, down_margin=1.3):
        if max_shards is None:
            max_shards = sharding.MAX_SHARDS
        self.engine = engine
        self.controller = autoscale.AutoscaleController(
            autoscale.AutoscaleConfig(
                target_rate=target_rate,
                min_workers=int(min_shards),
                max_workers=int(max_shards),
                window=window, cooldown=cooldown,
                up_margin=up_margin, down_margin=down_margin,
            )
        )
        self.splits = 0
        self.merges = 0
        self.refusals = 0

    def observe(self, round_s, *, healthy=True):
        """Fold one finished round's wall time; maybe resize.

        ``healthy=False`` marks a round where the shard plane already
        struggled (a failover mid-round, a starved reducer) — it maps
        to the controller's negative quorum margin, vetoing merges for
        a full window so a wobble is never compounded by a shrink.
        """
        s = self.engine.spec.num_shards
        act = self.controller.observe(
            float(round_s), active=s,
            quorum_margin=0 if healthy else -1,
        )
        if act == 0:
            return 0
        try:
            self.engine.resize(s + act)
        except ValueError:
            # Impossible resize (nibble cap / more shards than params):
            # the engine changed nothing, so the controller must
            # remember nothing — satellite-2's rescind contract.
            self.controller.rescind()
            self.refusals += 1
            return 0
        if act > 0:
            self.splits += 1
        else:
            self.merges += 1
        return act
