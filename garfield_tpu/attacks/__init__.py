"""Byzantine fault injection as pure, jit'd value transforms.

TPU-native counterpart of the reference's attack components:
  - gradient attacks: ``pytorch_impl/libs/garfieldpp/byzWorker.py`` (attack
    table :62-68, attacks :78-143) and ``tensorflow_impl/libs/attacker.py``
    (:36-127);
  - model attacks:    ``pytorch_impl/libs/garfieldpp/byzServer.py`` (attack
    table :74-78, attacks :86-108).

Design shift (SURVEY §7): the reference injects faults by *subclassing the
node role* and replacing its RPC response. On a TPU mesh every worker slot is
an SPMD shard of one jit'd program, so Byzantine behavior becomes a **value
transformation of the gathered gradient stack**: compute honest gradients for
every slot, then rewrite the rows selected by a boolean ``byz_mask``. This
keeps the whole fault-injection path on-device, inside jit, and differentiably
close to the reference semantics:

  - colluding attacks (lie / empire) need the ``fw`` honest gradients of the
    Byzantine cohort (byzWorker.py:114-117 computes them locally from extra
    batches); here the cohort's honest rows are already in the stack, so the
    collusion statistics (mu, sigma) are masked reductions over those rows;
  - randomized attacks thread an explicit ``jax.random`` key instead of torch
    global RNG, keeping steps reproducible and replay-exact.

Registries mirror the reference dicts, plus the crash fault:
  ``gradient_attacks``: random, reverse, drop, lie, empire, crash
  ``model_attacks``:    random, reverse, drop, crash
(``crash`` zeroes the dead slot's contribution — Garfield_CC's
``mar='crash'`` semantics — used by utils/multihost.FaultSchedule.)
"""

import jax
import jax.numpy as jnp

__all__ = [
    "gradient_attacks",
    "model_attacks",
    "apply_gradient_attack",
    "apply_gradient_attack_tree",
    "apply_model_attack",
    "apply_model_attack_rows",
    "GradientAttackFold",
    "plan_gradient_attack_fold",
    "plan_model_attack_fold",
    "note_attack_fallback",
    "reset_attack_fallback",
]


def _masked_moments(g, mask):
    """Mean and unbiased std over the rows of ``g`` selected by ``mask``.

    Matches ``torch.mean``/``torch.std`` over the stacked cohort gradients
    (byzWorker.py:119-121): std is Bessel-corrected (ddof=1), so a cohort of
    one (fw=1) yields sigma=NaN exactly as torch does — downstream GARs treat
    the resulting non-finite gradient as infinitely distant (krum.py:46-48),
    reproducing the reference's emergent behavior.
    """
    w = mask.astype(g.dtype)[:, None]
    count = jnp.sum(w)
    mu = jnp.sum(w * g, axis=0) / count
    var = jnp.sum(w * (g - mu[None, :]) ** 2, axis=0) / (count - 1.0)
    return mu, jnp.sqrt(var)


# --- gradient attacks (byzWorker.py:78-143) --------------------------------


# Reference attack defaults (byzWorker.py:108-143), shared by the direct
# attack functions AND the folded-plan builder so the two application paths
# can never drift apart.
LIE_Z = 1.035
EMPIRE_EPS = 10.0
REVERSE_FACTOR = -100.0


def random_attack(g, mask, *, key, **_):
    """Replace Byzantine rows with uniform[0,1) noise (byzWorker.py:78-85)."""
    fake = jax.random.uniform(key, g.shape, dtype=g.dtype)
    return jnp.where(mask[:, None], fake, g)


def reverse_attack(g, mask, *, factor=REVERSE_FACTOR, **_):
    """Amplified sign-flip: grad * -100 (byzWorker.py:87-94)."""
    return jnp.where(mask[:, None], g * factor, g)


def drop_attack(g, mask, *, key, p=0.3, **_):
    """Zero out a random 30% of coordinates (byzWorker.py:96-106)."""
    drop = jax.random.uniform(key, g.shape) > (1.0 - p)
    return jnp.where(mask[:, None] & drop, 0.0, g)


def lie_attack(g, mask, *, z=LIE_Z, **_):
    """Little-is-enough: mu + z*sigma over the colluding cohort's honest
    gradients (byzWorker.py:108-125; z_max=1.035 precomputed for n=20, f=8).
    """
    mu, sigma = _masked_moments(g, mask)
    fake = mu + z * sigma
    return jnp.where(mask[:, None], fake[None, :], g)


def empire_attack(g, mask, *, eps=EMPIRE_EPS, **_):
    """Fall-of-empires: -eps * mu over the colluding cohort
    (byzWorker.py:127-143; eps=10, empirical).
    """
    mu, _ = _masked_moments(g, mask)
    fake = -eps * mu
    return jnp.where(mask[:, None], fake[None, :], g)


def crash_attack(g, mask, **_):
    """Crash fault: the dead slots contribute all-zero gradients — what
    Garfield_CC's ``mar='crash'`` mode feeds the aggregation
    (Garfield_CC/trainer.py:97,137); used by the host-level fault
    simulation (utils/multihost.FaultSchedule)."""
    return jnp.where(mask[:, None], 0.0, g)


gradient_attacks = {
    "random": random_attack,
    "reverse": reverse_attack,
    "drop": drop_attack,
    "lie": lie_attack,
    "empire": empire_attack,
    "crash": crash_attack,
}

# Attacks that draw randomness (shared by both dispatchers below).
_NEEDS_KEY = {random_attack, drop_attack}
# Attacks that are coordinate-wise given per-coordinate masked row
# statistics — the invariant that makes per-LEAF application
# (apply_gradient_attack_tree) equivalent to flat application. A new
# attack must be added here explicitly to become tree-capable; otherwise
# the tree dispatcher rejects it instead of silently mis-applying it.
_COORDINATE_WISE = {
    random_attack, reverse_attack, drop_attack, lie_attack, empire_attack,
    crash_attack,
}


def _resolve_gradient_attack(attack, key):
    """Shared dispatch: name -> fn, with the needs-key check."""
    if attack not in gradient_attacks:
        raise ValueError(
            f"unknown attack {attack!r}; available: {sorted(gradient_attacks)}"
        )
    fn = gradient_attacks[attack]
    if fn in _NEEDS_KEY and key is None:
        raise ValueError(f"attack {attack!r} needs a PRNG key")
    return fn


def apply_gradient_attack(attack, gradients, byz_mask, *, key=None, **params):
    """Rewrite the Byzantine rows of a (n, d) gradient stack.

    Args:
      attack: name in ``gradient_attacks`` (byzWorker.py:62-68 table), or
        None/"none" for fault-free passthrough.
      gradients: (n, d) stack — one row per logical worker slot.
      byz_mask: (n,) bool — True rows are Byzantine.
      key: jax PRNG key; required by the randomized attacks (random, drop).
      **params: attack knobs (z, eps, p, factor) with reference defaults.

    Returns the poisoned (n, d) stack; honest rows are returned untouched.
    """
    if attack is None or attack == "none":
        return gradients
    fn = _resolve_gradient_attack(attack, key)
    mask = jnp.asarray(byz_mask, dtype=bool)
    if fn in _NEEDS_KEY:
        return fn(gradients, mask, key=key, **params)
    return fn(gradients, mask, **params)


def apply_gradient_attack_tree(attack, grads_tree, byz_mask, *, key=None,
                               **params):
    """Tree-mode twin of ``apply_gradient_attack``: poison the Byzantine rows
    of a stacked gradient TREE (leading n axis per leaf) leaf by leaf.

    Every gradient attack is coordinate-wise given the cohort row statistics,
    and lie/empire's mu/sigma are per-coordinate masked reductions — so
    applying the (n, d)-stack attack to each leaf reshaped to (n, size) is
    semantically identical to flattening first. Randomized attacks fold the
    key per leaf, so their draws differ from the flat path bitwise but not in
    distribution. Used by the tree-mode GAR fast path
    (parallel/aggregathor.py; PERF.md).
    """
    if attack is None or attack == "none":
        return grads_tree
    fn = _resolve_gradient_attack(attack, key)
    if fn not in _COORDINATE_WISE:
        raise ValueError(
            f"attack {attack!r} is not coordinate-wise; per-leaf application "
            "would use wrong cohort statistics — use the flat path"
        )
    mask = jnp.asarray(byz_mask, dtype=bool)

    leaves, treedef = jax.tree.flatten(grads_tree)
    out = []
    for i, leaf in enumerate(leaves):
        n = leaf.shape[0]
        flat = leaf.reshape(n, -1)
        kw = dict(params)
        if fn in _NEEDS_KEY:
            kw["key"] = jax.random.fold_in(key, i)
        out.append(fn(flat, mask, **kw).reshape(leaf.shape))
    return jax.tree.unflatten(treedef, out)


# --- folded (algebraic) attack application ---------------------------------
#
# The deterministic attacks have row-level structure a Gram-based GAR can
# exploit without ever writing the poisoned rows:
#   - lie / empire publish ONE shared fake vector from all Byzantine slots
#     (byzWorker.py:108-143: every colluding worker submits mu + z*sigma /
#     -eps*mu) -> append the fake as ONE extra stack row and remap;
#   - reverse scales each Byzantine row by a constant (byzWorker.py:87-94)
#     -> scale Gram rows/cols and the selection weights;
#   - crash zeroes the row -> scale 0.
# The poisoned Gram is then a static row remap + outer scaling of the raw
# (n+k, n+k) Gram, and the GAR's weighted row sum is one matvec over the
# extended stack. The raw Gram keeps fusing into the backward epilogue
# exactly like the fault-free step — the whole-tree `where` rewrite, which
# forces the stacked gradient tree to rematerialize, never happens. Measured
# 1.16x on the north-star krum+lie step (PERF.md round 4); the randomized
# attacks (random, drop) have no such structure and keep the `where` path.


class GradientAttackFold:
    """Static plan for applying a gradient attack inside a Gram-based GAR.

    Poisoned row i == ``row_scale[i] * extended_stack[row_map[i]]`` where
    ``extended_stack`` is the raw (n, ...) stack with ``num_extra`` (0 or 1)
    shared fake rows appended. All fields are static (numpy) except
    ``build_extra``, which builds the fake row tree from the stacked raw
    gradients at trace time. Consumed by ``parallel.fold``.
    """

    def __init__(self, row_map, row_scale, build_extra=None):
        import numpy as np

        self.row_map = np.asarray(row_map, dtype=np.int32)
        self.row_scale = np.asarray(row_scale, dtype=np.float32)
        self.build_extra = build_extra
        self.num_extra = 1 if build_extra is not None else 0


def _shared_fake_builder(byz_idx, count, transform):
    """Per-leaf shared fake row from the Byzantine cohort's honest rows.

    Moments are accumulated in f32 and agree with ``_masked_moments`` to
    f32 rounding (the masked sum reduces n terms, this one the fw gathered
    terms — same values, possibly different association, so last-ulp
    differences are possible); for bf16 pipelines the f32 accumulation is
    *better* than the where-path's leaf-dtype sums and the two paths agree
    only to bf16 rounding.
    """

    def build_extra(stacked_tree):
        def one(leaf):
            s = leaf[byz_idx].astype(jnp.float32)
            mu = jnp.sum(s, axis=0) / count
            var = jnp.sum((s - mu[None]) ** 2, axis=0) / (count - 1.0)
            return transform(mu, jnp.sqrt(var)).astype(leaf.dtype)

        return jax.tree.map(one, stacked_tree)

    return build_extra


# One-time attack_fallback telemetry guard: the randomized attacks
# (random, drop) have no folded form and silently keep the where-path —
# benches comparing fold-path wins must see that attributed, not infer it
# (docs/TELEMETRY.md v7). One event per (attack, why) per process.
_FALLBACK_EMITTED = set()


def note_attack_fallback(attack, *, path, why):
    """Emit the one-time ``attack_fallback`` telemetry event: ``attack``
    is taking ``path`` (e.g. "where") instead of the folded fast path
    because ``why``. No-op when no MetricsHub is installed, and at most
    once per (attack, why) per process so per-step plan rebuilds cannot
    flood the stream."""
    key = (str(attack), str(why))
    if key in _FALLBACK_EMITTED:
        return
    _FALLBACK_EMITTED.add(key)
    from ..telemetry import hub as _hub

    _hub.emit_event(
        "attack_fallback", attack=str(attack), path=str(path), why=str(why)
    )


def reset_attack_fallback():
    """Test hook: forget which fallbacks were already reported."""
    _FALLBACK_EMITTED.clear()


def plan_gradient_attack_fold(attack, byz_mask, *, z=LIE_Z, eps=EMPIRE_EPS,
                              factor=REVERSE_FACTOR, **_):
    """Return the ``GradientAttackFold`` for ``attack``, or None when the
    attack has no folded form (randomized rows, or no Byzantine slots, or
    ``GARFIELD_NO_FOLD`` set to any non-empty value — the A/B escape
    hatch)."""
    import os

    import numpy as np

    if attack is None or attack == "none" or os.environ.get("GARFIELD_NO_FOLD"):
        return None
    if attack in ("random", "drop"):
        # The silent half of the fold dispatch, made loud (schema v7):
        # these rows are freshly random every step, so there is no static
        # remap+scale — the topology keeps the where-path.
        note_attack_fallback(
            attack, path="where", why="randomized attack has no folded form"
        )
        return None
    mask = np.asarray(byz_mask, dtype=bool)
    n = mask.size
    byz_idx = np.flatnonzero(mask)
    if byz_idx.size == 0:
        return None
    identity = np.arange(n)
    ones = np.ones(n)
    if attack == "lie":
        return GradientAttackFold(
            np.where(mask, n, identity), ones,
            _shared_fake_builder(
                byz_idx, float(byz_idx.size),
                lambda mu, sigma: mu + z * sigma,
            ),
        )
    if attack == "empire":
        return GradientAttackFold(
            np.where(mask, n, identity), ones,
            _shared_fake_builder(
                byz_idx, float(byz_idx.size), lambda mu, sigma: -eps * mu
            ),
        )
    if attack == "reverse":
        return GradientAttackFold(identity, np.where(mask, factor, 1.0))
    if attack == "crash":
        return GradientAttackFold(identity, np.where(mask, 0.0, 1.0))
    return None


def plan_model_attack_fold(attack, byz_mask, *, factor=-100.0, **_):
    """Folded plan for the DETERMINISTIC model attacks, or None.

    byzServer's reverse (model * -100, :93-98) and the crash fault are pure
    per-row scalings with no cohort statistics and no shared fake row, so
    their ``GradientAttackFold`` is an identity row map with scales — the
    Gram-remap machinery of ``parallel.fold`` applies to model-plane
    exchanges (LEARN gossip, ByzSGD gather step) unchanged. Randomized
    model attacks (random, drop) keep the where-path. Same
    ``GARFIELD_NO_FOLD`` escape hatch as the gradient plans."""
    import os

    import numpy as np

    if attack is None or attack == "none" or os.environ.get("GARFIELD_NO_FOLD"):
        return None
    mask = np.asarray(byz_mask, dtype=bool)
    if not mask.any():
        return None
    identity = np.arange(mask.size)
    if attack == "reverse":
        return GradientAttackFold(identity, np.where(mask, factor, 1.0))
    if attack == "crash":
        return GradientAttackFold(identity, np.where(mask, 0.0, 1.0))
    return None


# --- model attacks (byzServer.py:86-108) -----------------------------------


def model_random_attack(m, *, key, **_):
    """Random model of the same shape (byzServer.py:86-91)."""
    return jax.random.uniform(key, m.shape, dtype=m.dtype)


def model_reverse_attack(m, *, factor=-100.0, **_):
    """model * -100 (byzServer.py:93-98)."""
    return m * factor


def model_drop_attack(m, *, key, p=0.3, **_):
    """Zero a random 30% of model coordinates (byzServer.py:100-108)."""
    drop = jax.random.uniform(key, m.shape) > (1.0 - p)
    return jnp.where(drop, 0.0, m)


def model_crash_attack(m, **_):
    """Crash fault: a dead node serves an all-zero model (the model-space
    twin of ``crash_attack``; a crashed host cannot gossip its state)."""
    return jnp.zeros_like(m)


model_attacks = {
    "random": model_random_attack,
    "reverse": model_reverse_attack,
    "drop": model_drop_attack,
    "crash": model_crash_attack,
}

# --- model-plane collusion attacks (DESIGN.md §17) --------------------------
#
# The PAPERS.md attacks (lie = mu + z*sigma, empire = -eps*mu) are
# gradient-plane INSTANCES of a strategy that works at any aggregation
# point: hide inside the spread of whatever rows the rule aggregates. On
# the model planes (ByzSGD's gather step, LEARN's gossip) the "cohort" a
# Byzantine publisher hides inside is the WHOLE gathered replica stack —
# unlike the gradient plane it need not simulate colluders, every row it
# wants statistics over is handed to it by the protocol itself. These are
# STACK-level attacks (they need the peers' rows), so they live beside
# ``apply_model_attack_rows`` and are dispatched by it; the single-vector
# ``apply_model_attack`` path (a lone Byzantine PS poisoning only its own
# publish, no peer visibility at poison time) is served host-side by
# apps/cluster.py keeping the previous round's gathered stack.


def model_lie_attack_rows(models, mask, *, z=LIE_Z, **_):
    """Model-plane little-is-enough: every Byzantine row publishes
    ``mu + z*sigma`` with mu/sigma the coordinate-wise moments of ALL
    gathered models (Bessel std, like the gradient twin)."""
    mu = jnp.mean(models, axis=0)
    n = models.shape[0]
    var = jnp.sum((models - mu[None]) ** 2, axis=0) / (n - 1.0)
    fake = mu + z * jnp.sqrt(var)
    return jnp.where(mask[:, None], fake[None, :], models)


def model_empire_attack_rows(models, mask, *, eps=EMPIRE_EPS, **_):
    """Model-plane fall-of-empires: ``-eps * mu`` over the gathered
    stack from every Byzantine row."""
    fake = -eps * jnp.mean(models, axis=0)
    return jnp.where(mask[:, None], fake[None, :], models)


# Stack-form model attacks (need the gathered rows; the single-vector
# dispatch below rejects them — a row-less call site has no cohort).
model_collusion_attacks = {
    "lie": model_lie_attack_rows,
    "empire": model_empire_attack_rows,
}


def apply_model_attack(attack, model_vec, *, key=None, **params):
    """Poison a flattened model vector a Byzantine PS would serve
    (byzServer.py:80-84 dispatch). ``attack`` None/"none" is passthrough.
    """
    if attack is None or attack == "none":
        return model_vec
    if attack in model_collusion_attacks:
        raise ValueError(
            f"model attack {attack!r} is a collusion statistic over the "
            "gathered stack; use apply_model_attack_rows (or the host "
            "roles' last-gather path)"
        )
    if attack not in model_attacks:
        raise ValueError(
            f"unknown model attack {attack!r}; available: {sorted(model_attacks)}"
        )
    fn = model_attacks[attack]
    if fn in (model_random_attack, model_drop_attack):
        if key is None:
            raise ValueError(f"model attack {attack!r} needs a PRNG key")
        return fn(model_vec, key=key, **params)
    return fn(model_vec, **params)


def apply_model_attack_rows(attack, models, byz_mask, *, key=None, **params):
    """Poison the Byzantine ROWS of a gathered (n, d) model stack.

    The stack form of ``apply_model_attack`` shared by the model planes
    (LEARN gossip, ByzSGD gather step): row i is attacked with the key
    folded by its GLOBAL row index, so every shard derives identical
    draws for the randomized attacks. The collusion statistics
    (lie/empire, DESIGN.md §17) are stack-only and dispatch here too.
    None/"none" is passthrough.
    """
    if attack is None or attack == "none":
        return models
    if attack in model_collusion_attacks:
        return model_collusion_attacks[attack](
            models, jnp.asarray(byz_mask, bool), **params
        )
    if attack not in model_attacks:
        raise ValueError(
            f"unknown model attack {attack!r}; available: {sorted(model_attacks)}"
        )
    fn = model_attacks[attack]
    n = models.shape[0]
    if fn in (model_random_attack, model_drop_attack):
        if key is None:
            raise ValueError(f"model attack {attack!r} needs a PRNG key")
        poisoned = jax.vmap(
            lambda i, m: fn(m, key=jax.random.fold_in(key, i), **params)
        )(jnp.arange(n), models)
    else:
        poisoned = jax.vmap(lambda m: fn(m, **params))(models)
    return jnp.where(jnp.asarray(byz_mask, bool)[:, None], poisoned, models)
