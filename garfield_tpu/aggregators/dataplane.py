"""Data-plane defense: per-class gradient fingerprints + two detectors.

The one cell the GAR-side stack cannot touch (XLA:CPU, round 15; DESIGN.md
§17): a low-``poison_frac`` BadNets backdoor submits HONEST gradients of
a poisoned task — in-distribution rows, nothing divergence-shaped for
Gram distances, suspicion weighting or the escalation ladder to measure
(``backdoor_asr_defended`` ~0.62 through the full krum→multi-krum→bulyan
ladder). What a data poisoner cannot hide is the PER-CLASS structure of
its classifier-head gradient: relabeling its samples as the target class
concentrates loss mass on that class's logit, so the head-gradient row
for the target class (and its bias component — the batch's summed logit
error) departs coherently from the honest crowd's. This module measures
exactly that:

  - **Fingerprints** (``fingerprints``): the classifier-head block of
    each submitted gradient — located by ``head_spec`` (flat wire rows,
    the host PS) or ``head_leaves`` (the stacked gradient tree, in-graph)
    and reshaped to a (num_classes, feat) matrix — reduced to fixed-shape
    per-class statistics: crowd-normalized per-class row norms, cosine
    projections onto the crowd's per-class head direction, and the bias
    gradient's per-class z-scores. Shape (n, 3*num_classes) (2*C without
    a bias), independent of d — cheap at any model scale, jit-safe.
  - **Spectral filtering** (``spectral_scores``; Tran et al., NeurIPS
    2018 "spectral signatures"): outlier scores along the top singular
    vector of the CENTERED fingerprint matrix (fixed-iteration power
    iteration on the (k, k) covariance — no data-dependent shapes).
    Scores are |projection| / rms(projection); ranks beyond the
    ``tau``-sigma tail are flagged.
  - **Head-gradient 2-means** (``cluster_flags``; Chen et al. 2018
    activation-clustering, applied to head GRADIENTS — the quantity the
    PS actually holds): fixed-iteration Lloyd over the suspect target
    class's head rows (``suspect_class`` picks the class whose bias
    z-scores disperse most). A trigger cohort forms a small, tight,
    well-separated cluster; its members are flagged iff the cluster is
    no larger than the declared ``f`` budget AND the between-center
    separation clears the within-cluster spread.

Both detectors are dual-backend (numpy on the host PS quorums, traced
jnp in the on-mesh step — the TapBundle convention: traced OUT entirely
when the data defense is off) and feed the EXISTING suspicion algebra:
per-round flags fold into a decayed exclusion EMA (the MetricsHub
halflife law), and ``defense.suspicion_weights`` maps the EMA's
suspicion through the same median-relative floored WEIGHT LAW the
staleness and GAR-suspicion discounts use. A clean history therefore
weighs exactly 1.0, and occasional single-round false flags wash out in
the EMA instead of down-weighting an honest rank. The COMPOSITION of
those weights is deliberately different, and the measured negative
result behind it is recorded here: multiplying data-plane weights into
the row-scale slot (the staleness algebra) made defense_bench's backdoor
cell WORSE than undefended (ASR 0.97 vs 0.10) — a toward-zero-scaled
cohort row lands where late-training honest gradients cluster, so krum
ADMITS it (the same inlier inversion that puts r02's
``backdoor/escalate`` at 0.62). Data-plane weights therefore compose by
CENTER-PULL (``center_pull_rows``/``center_pull_tree``): suspect rows
collapse onto the stack's coordinate median, so a fully-flagged row is
selectable but informationless.

``DataPlaneDefense`` is the host-side deployment (a ``PlaneDefense``
sibling) for the SSMW/MSMW PS gradient quorums: it fingerprints the wire
frames the PS already decoded, carries the per-rank EMA, and serves
per-quorum weights + the schema-v9 ``data_defense`` telemetry payload.
"""

import dataclasses

import numpy as np

__all__ = [
    "HeadSpec",
    "head_spec",
    "head_leaves",
    "head_from_rows",
    "fingerprints",
    "spectral_scores",
    "suspect_class",
    "cluster_flags",
    "detect",
    "center_pull_rows",
    "center_pull_tree",
    "DataPlaneDefense",
]

# Detector defaults (overridable via --defense_params dp_*): the spectral
# tail threshold, Lloyd/power iteration counts, and the 2-means
# separation gate (between-center distance^2 must exceed SEP x the mean
# within-cluster variance before the small cluster is called a cohort).
DEFAULT_TAU = 2.0
POWER_ITERS = 8
LLOYD_ITERS = 8
CLUSTER_SEP = 4.0
_EPS = 1e-12


@dataclasses.dataclass(frozen=True)
class HeadSpec:
    """Static location of the classifier head inside the flat gradient.

    ``kernel`` is the (start, end) ravel-order span of the head's
    (feat, classes) kernel; ``bias`` the span of its (classes,) bias, or
    None when the kernel has no adjacent bias leaf. Derived once from a
    params TEMPLATE (``head_spec``), then applied to every wire row the
    PS decodes — the host twin of the in-graph ``head_leaves``.
    """

    kernel: tuple
    bias: tuple
    feat: int
    classes: int


def _key_str(k):
    # jax path entries are DictKey/GetAttrKey/SequenceKey wrappers; pull
    # the underlying name out so flax param dicts yield plain strings.
    for attr in ("key", "name", "idx"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k)


def _named_leaves(tree):
    import jax
    import jax.numpy as jnp

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out, start = [], 0
    for path, leaf in flat:
        size = int(np.prod(jnp.shape(leaf))) if jnp.ndim(leaf) else 1
        out.append((
            tuple(_key_str(k) for k in path), leaf, (start, start + size)
        ))
        start += size
    return out


def _head_index(named, kernel_ndim):
    """Flatten-order index of the classifier-head kernel, or None.

    Resolution hierarchy (the transformer family broke the old "last
    2-D leaf" rule — flax flattens by SORTED string key, so ViT's
    ``pos_embedding`` param (lowercase sorts after every capitalized
    module scope) and GPT's nested ``EncoderBlock_*`` MLP kernels all
    flatten AFTER the top-level ``Dense_0`` head):

      1. the highest-numbered TOP-LEVEL ``Dense_i/kernel`` — flax's
         auto-naming for the final projection of every zoo model that
         has one (CNNs and transformers alike);
      2. a model with an ``nn.Embed`` table (final path key
         ``embedding``) but NO top-level Dense head ties its output
         head to the embedding (``Embed.attend``) — there is no head
         gradient distinct from the embedding gradient to fingerprint,
         so this REFUSES loudly rather than silently fingerprinting
         some interior matrix;
      3. the last ``kernel``-named leaf of head rank (nested heads in
         hand-rolled scopes);
      4. the last leaf of head rank (non-flax trees with no string
         naming — the legacy rule, still exercised by raw-dict tests).
    """
    top_dense, top_i = None, -1
    last_kernel = None
    last_nd = None
    has_embed = False
    for i, (path, leaf, _span) in enumerate(named):
        nd = int(np.ndim(leaf)) if not hasattr(leaf, "ndim") else int(
            leaf.ndim
        )
        if path and path[-1] == "embedding":
            has_embed = True
        if nd != kernel_ndim:
            continue
        last_nd = i
        if not path or path[-1] != "kernel":
            continue
        last_kernel = i
        if len(path) == 2 and path[0].startswith("Dense_"):
            try:
                di = int(path[0].rsplit("_", 1)[1])
            except ValueError:
                continue
            if di > top_i:
                top_i, top_dense = di, i
    if top_dense is not None:
        return top_dense
    if has_embed:
        raise ValueError(
            "data-plane defense cannot fingerprint an embedding-tied "
            "head: the params carry an nn.Embed table but no top-level "
            "Dense head (the GPT(tied=True) layout, and the lfm2 family "
            "of models/lfm2.py, whose logits are always its embedding's) "
            "— the output head IS the embedding gradient, which every "
            "token in the batch touches, so no per-class head block "
            "exists. Use an untied head (GPT tied=False) to run the "
            "data-plane defense; the lfm2 family runs with it off."
        )
    if last_kernel is not None:
        return last_kernel
    return last_nd


def head_spec(params):
    """``HeadSpec`` of a params tree, or None when no head is found.

    The classifier head is located by ``_head_index`` (top-level
    ``Dense_{max}`` kernel first; embedding-tied layouts REFUSE with a
    ValueError; legacy last-matrix fallbacks for hand-rolled trees);
    its trailing dim is the class count. The bias is the immediately
    preceding leaf when that is a matching (classes,)-vector (flax
    sorts ``bias`` before ``kernel`` inside one Dense scope). Models
    without any matrix leaf get None and the data-plane defense
    refuses loudly at the caller.
    """
    import jax.numpy as jnp

    named = _named_leaves(params)
    k_idx = _head_index(named, 2)
    if k_idx is None:
        return None
    leaf = named[k_idx][1]
    feat, classes = (int(s) for s in jnp.shape(leaf))
    bias = None
    if k_idx > 0:
        prev = named[k_idx - 1][1]
        if jnp.ndim(prev) == 1 and int(jnp.shape(prev)[0]) == classes:
            bias = named[k_idx - 1][2]
    return HeadSpec(
        kernel=named[k_idx][2], bias=bias, feat=feat, classes=classes
    )


def head_leaves(stacked_tree):
    """(kernel (n, classes, feat), bias (n, classes) or None) from a
    STACKED gradient tree (leading rank axis per leaf) — the in-graph
    twin of ``head_spec`` + ``head_from_rows``, selected statically at
    trace time so nothing head-shaped exists in the program when the
    defense is off. The head kernel is resolved by the SAME hierarchy
    as ``head_spec`` (one rank higher: rank axis + the (feat, classes)
    matrix); rows are transposed to class-major.
    """
    import jax.numpy as jnp

    named = _named_leaves(stacked_tree)
    k_idx = _head_index(named, 3)
    if k_idx is None:
        return None, None
    kernel = jnp.swapaxes(named[k_idx][1], 1, 2)  # (n, classes, feat)
    classes = kernel.shape[1]
    bias = None
    if k_idx > 0:
        prev = named[k_idx - 1][1]
        if prev.ndim == 2 and prev.shape[1] == classes:
            bias = prev
    return kernel, bias


def head_from_rows(spec, rows):
    """Extract (kernel (n, classes, feat), bias (n, classes) or None)
    from flat (n, d) gradient rows — the wire frames the PS decoded."""
    xp = _xp(rows)
    n = rows.shape[0]
    s, e = spec.kernel
    kernel = xp.swapaxes(
        rows[:, s:e].reshape(n, spec.feat, spec.classes), 1, 2
    )
    bias = None
    if spec.bias is not None:
        bs, be = spec.bias
        bias = rows[:, bs:be]
    return kernel, bias


def _xp(x):
    import jax

    if isinstance(x, jax.Array):
        import jax.numpy as jnp

        return jnp
    return np


def fingerprints(kernel, bias=None):
    """(n, k) per-rank fingerprints from class-major head gradients.

    Three fixed-shape per-class statistics, each scale-free against the
    crowd (a lone magnitude outlier is the GAR plane's job; the data
    plane keys on per-class STRUCTURE):

      - crowd-normalized row norms ``||H_i[c]|| / mean_j ||H_j[c]||`` —
        a cohort concentrating loss on one class inflates that class's
        row against the crowd;
      - cosine projections onto the crowd's class direction
        ``<H_i[c], u_c> / ||H_i[c]||`` with ``u_c`` the normalized crowd
        sum — a relabeling cohort's target-class row points AGAINST the
        honest direction (it pushes the logit the other way);
      - bias z-scores ``(b_ic - mean) / std`` (when the head has a
        bias) — the summed per-class logit error of the rank's batch,
        the label-distribution signal a relabeled batch cannot mask.

    Accumulates in f32 (bf16 pipelines round norm sums), dual-backend.
    """
    xp = _xp(kernel)
    H = kernel.astype(xp.float32)
    r = xp.sqrt(xp.sum(H * H, axis=-1) + _EPS)  # (n, C)
    r_norm = r / (xp.mean(r, axis=0, keepdims=True) + _EPS)
    u = xp.sum(H, axis=0)  # (C, feat) crowd sum per class
    u = u / (xp.sqrt(xp.sum(u * u, axis=-1, keepdims=True)) + _EPS)
    proj = xp.sum(H * u[None], axis=-1) / r  # (n, C) cosine
    cols = [r_norm, proj]
    if bias is not None:
        b = bias.astype(xp.float32)
        bz = (b - xp.mean(b, axis=0, keepdims=True)) / (
            xp.std(b, axis=0, keepdims=True) + _EPS
        )
        cols.append(bz)
    return xp.concatenate(cols, axis=-1)


def spectral_scores(fp, iters=POWER_ITERS):
    """(n,) spectral outlier scores over a fingerprint matrix.

    Tran et al.'s spectral-signature statistic on the fingerprint space:
    center, power-iterate the (k, k) covariance to the top singular
    direction (deterministic ones-init — the fingerprint columns are
    crowd-normalized, so no column dominates degenerately), and score
    each rank by |projection| / rms(projection). Dimensionless: ~1 for
    the crowd, >> 1 for a coherent minority, so a single ``tau``
    threshold serves every task. Fixed iteration count and shapes —
    jit-safe; numpy in, numpy out on the host.
    """
    xp = _xp(fp)
    X = fp.astype(xp.float32)
    X = X - xp.mean(X, axis=0, keepdims=True)
    C = X.T @ X  # (k, k)
    v = xp.ones((C.shape[0],), xp.float32) / np.sqrt(C.shape[0])
    for _ in range(int(iters)):
        v = C @ v
        v = v / (xp.sqrt(xp.sum(v * v)) + _EPS)
    s = X @ v  # (n,) signed projections
    sigma = xp.sqrt(xp.mean(s * s) + _EPS)
    return xp.abs(s) / sigma


def suspect_class(kernel, bias=None):
    """Index of the class the data-plane evidence points at: the class
    whose bias statistics (or, bias-less, crowd-normalized row norms)
    disperse the most across ranks — a relabeling cohort concentrates
    its departure on the TARGET class's statistics. Traced-argmax safe.

    Dispersion is measured ROBUSTLY (|x - median| / MAD), not by
    mean/std z-scores: a cohort of f coherent outliers corrupts the
    mean and inflates the std of its OWN class, capping the classic
    z at ~sqrt((n-f)/f) — at f/n = 1/4 that is 1.73, and a single
    noisy rank in a quiet class beats it, steering the 2-means at the
    wrong rows (the token-backdoor cell that exposed this: 8 workers,
    f=2, target-class bias gradient -0.9 vs honest 0.05, and the old
    statistic picked a clean class). Median/MAD stay anchored to the
    honest crowd for any cohort below n/2, so the target class's z is
    unbounded in the departure size. Per-class MADs are floored by a
    fraction of their crowd median so a near-constant class cannot win
    on numerical noise.
    """
    xp = _xp(kernel)
    if bias is not None:
        stat = bias.astype(xp.float32)
    else:
        H = kernel.astype(xp.float32)
        stat = xp.sqrt(xp.sum(H * H, axis=-1) + _EPS)
    med = xp.median(stat, axis=0, keepdims=True)
    dev = xp.abs(stat - med)
    mad = xp.median(dev, axis=0, keepdims=True)
    floor = 0.01 * xp.mean(mad) + _EPS
    z = dev / (mad + floor)
    return xp.argmax(xp.max(z, axis=0))


def cluster_flags(rows, f, iters=LLOYD_ITERS, sep=CLUSTER_SEP):
    """(n,) bool flags from 2-means over one class's head-gradient rows.

    Fixed-iteration Lloyd (jit-safe: masked means, no data-dependent
    shapes), initialized at the extreme rows along the rows' own top
    singular direction (the spectral init — deterministic and
    permutation-equivariant). The SMALLER cluster is flagged iff

      - its size is within the declared Byzantine budget ``f`` (a
        "small cluster" of n/2 is a data modality, not a cohort), and
      - the squared between-center distance exceeds ``sep`` times the
        mean within-cluster variance (honest minibatch noise forms no
        such gap; a trigger cohort — near-identical poisoned batches —
        does).

    Returns all-False when the gates fail, so clean runs see no
    cluster evidence. Dual-backend.
    """
    xp = _xp(rows)
    X = rows.astype(xp.float32)
    n = X.shape[0]
    Xc = X - xp.mean(X, axis=0, keepdims=True)
    C = Xc.T @ Xc
    v = xp.ones((C.shape[0],), xp.float32) / np.sqrt(C.shape[0])
    for _ in range(int(iters)):
        v = C @ v
        v = v / (xp.sqrt(xp.sum(v * v)) + _EPS)
    t = Xc @ v
    c0 = X[xp.argmin(t)]
    c1 = X[xp.argmax(t)]
    assign = None
    for _ in range(int(iters)):
        d0 = xp.sum((X - c0[None]) ** 2, axis=-1)
        d1 = xp.sum((X - c1[None]) ** 2, axis=-1)
        assign = d1 < d0  # True -> cluster 1
        w1 = assign.astype(xp.float32)
        w0 = 1.0 - w1
        # Masked means with empty-cluster guards (keep the old center).
        n0 = xp.sum(w0)
        n1 = xp.sum(w1)
        m0 = (w0[:, None] * X).sum(axis=0) / xp.maximum(n0, 1.0)
        m1 = (w1[:, None] * X).sum(axis=0) / xp.maximum(n1, 1.0)
        c0 = xp.where(n0 > 0, m0, c0)
        c1 = xp.where(n1 > 0, m1, c1)
    w1 = assign.astype(xp.float32)
    w0 = 1.0 - w1
    n0 = xp.sum(w0)
    n1 = xp.sum(w1)
    small_is_1 = n1 <= n0
    small_w = xp.where(small_is_1, w1, w0)
    small_n = xp.minimum(n0, n1)
    between = xp.sum((c0 - c1) ** 2)
    within = (
        xp.sum(w0 * xp.sum((X - c0[None]) ** 2, axis=-1))
        + xp.sum(w1 * xp.sum((X - c1[None]) ** 2, axis=-1))
    ) / xp.maximum(xp.asarray(n, xp.float32), 1.0)
    ok = (
        (small_n >= 1.0)
        & (small_n <= xp.asarray(float(max(1, int(f))), xp.float32))
        & (between > sep * (within + _EPS))
    )
    return (small_w > 0.5) & ok


def detect(kernel, bias, *, f, tau=DEFAULT_TAU):
    """Run both detectors over one quorum's head gradients.

    Returns ``(scores, flags)``: the (n,) spectral outlier scores and
    the (n,) bool union of the tau-sigma spectral tail and the 2-means
    cohort flags over the suspect class's rows. Dual-backend — this is
    the single entry the in-graph step and the host ``DataPlaneDefense``
    both call, so the two deployments can never disagree on the math.
    """
    xp = _xp(kernel)
    fp = fingerprints(kernel, bias)
    scores = spectral_scores(fp)
    cls = suspect_class(kernel, bias)
    if xp is np:
        rows = kernel[:, int(cls), :]
    else:
        import jax.numpy as jnp

        rows = jnp.take(kernel, cls, axis=1)
    cflags = cluster_flags(rows, f)
    flags = (scores > tau) | cflags
    return scores, flags


def center_pull_rows(rows, w):
    """Data-plane weight COMPOSITION: pull suspect rows onto the
    TRUSTED center, ``row_i' = c + w_i * (row_i - c)`` with ``c`` the
    dp-weight-weighted mean of the stack (``sum_j w_j row_j / sum_j
    w_j`` — rows the EMA trusts at ~1.0 define it; flagged rows barely
    contribute).

    Two measured negative results shaped this (defense_bench probes,
    recorded in DESIGN.md §18):

      - Plain row SCALING (the staleness/GAR-suspicion algebra) is the
        wrong composition for data-plane evidence against proximity
        rules: a 0.1-scaled backdoor row lands near the ORIGIN, which
        is exactly where late-training honest gradients cluster, so
        krum ADMITS the scaled cohort — ASR 0.97 vs undefended 0.10,
        the same inlier inversion that puts r02's ``backdoor/escalate``
        at 0.62 (any toward-zero dampening of a data poisoner hands it
        centrality).
      - Pulling onto the RAW stack's coordinate median still leaked: a
        coherent f-cohort at one extreme shifts the contaminated
        median by an order statistic, and the rule (which now happily
        selects the central pulled rows) re-injects that bias every
        step — the defended model's target-emission base rate sat
        ~0.05 above the clean model's for the whole run.

    The trusted-mean center closes both: a fully-suspect row becomes
    the trusted rows' average — selectable but informationless — while
    honest rows at weight exactly 1.0 keep their values up to one float
    add/subtract (accuracy-level identity; the BITWISE contract applies
    to defense-off, which traces none of this). The per-rank
    radius-by-suspicion shape is centered clipping (cclip) with the
    radius driven by data-plane evidence instead of a norm bound.
    A cohort oscillating its weight around 0.5 both contributes to the
    center and keeps deviation — bounded at half strength, and the GAR
    plane still audits whatever residual it plays.
    """
    xp = _xp(rows)
    wv = xp.asarray(w, xp.float32)
    r32 = rows.astype(xp.float32)
    c = (wv[:, None] * r32).sum(axis=0) / xp.maximum(
        wv.sum(), xp.float32(1e-3)
    )
    out = c[None] + wv[:, None] * (r32 - c[None])
    return out.astype(rows.dtype)


def center_pull_tree(stacked_tree, w):
    """``center_pull_rows`` over a stacked gradient TREE (leading rank
    axis per leaf): per-leaf trusted-mean centers, one fused
    multiply-add per leaf — no (n, d) flat stack, so the tree/fold fast
    paths keep their layout (the transform is a per-leaf elementwise op
    exactly like the worker-momentum update)."""
    import jax
    import jax.numpy as jnp

    wv = jnp.asarray(w, jnp.float32)
    denom = jnp.maximum(wv.sum(), jnp.float32(1e-3))

    def one(leaf):
        wl = wv.reshape((leaf.shape[0],) + (1,) * (leaf.ndim - 1))
        l32 = leaf.astype(jnp.float32)
        c = (wl * l32).sum(axis=0, keepdims=True) / denom
        return (c + wl * (l32 - c)).astype(leaf.dtype)

    return jax.tree.map(one, stacked_tree)


class DataPlaneDefense:
    """Host-side data-plane defense for ONE PS gradient plane.

    The ``PlaneDefense`` sibling (aggregators/defense.py) for the third
    plane of the closed loop: per-round detector flags fold into a
    decayed per-rank exclusion EMA (the MetricsHub halflife law — a
    cohort cannot launder the score by pausing), and
    ``defense.suspicion_weights`` maps the EMA through the same
    median-relative floored row-weight path as every other discount.
    ``observe`` ingests one quorum's decoded wire rows; ``weights_for``
    returns the per-quorum-row weights, or None when every weight is
    exactly 1.0 (the caller dispatches the unweighted program — the
    clean-history identity the bitwise contract needs).
    """

    def __init__(self, num_ranks, spec, *, f, plane="gradient",
                 tau=DEFAULT_TAU, power=4.0, floor=0.0, halflife=8.0):
        if spec is None:
            raise ValueError(
                "data-plane defense needs a classifier head "
                "(head_spec found no 2-D parameter leaf)"
            )
        if halflife <= 0.0:
            raise ValueError(f"dp halflife must be > 0, got {halflife}")
        if tau <= 0.0:
            raise ValueError(f"dp tau must be > 0, got {tau}")
        self.num_ranks = int(num_ranks)
        self.spec = spec
        self.f = max(1, int(f))
        self.plane = str(plane)
        self.tau = float(tau)
        self.power = float(power)
        self.floor = float(floor)
        self._decay = 0.5 ** (1.0 / float(halflife))
        self._obs = np.zeros(self.num_ranks, np.float64)
        self._exc = np.zeros(self.num_ranks, np.float64)
        self.rounds = 0
        self.flagged_total = 0
        self.last_scores = np.zeros(self.num_ranks, np.float64)

    def observe(self, ranks, rows):
        """Fingerprint one quorum's flat rows, fold the flags into the
        EMA; returns {"scores", "flags"} over the quorum (taps order).

        Quorums of fewer than 4 rows carry no crowd to depart from —
        the detectors are skipped (zero scores, no flags) rather than
        thresholding noise.
        """
        ranks = np.asarray(ranks, np.int64)
        rows = np.asarray(rows, np.float32)
        q = rows.shape[0]
        if q < 4:
            scores = np.zeros(q, np.float64)
            flags = np.zeros(q, bool)
        else:
            kernel, bias = head_from_rows(self.spec, rows)
            scores, flags = detect(kernel, bias, f=self.f, tau=self.tau)
            scores = np.asarray(scores, np.float64)
            flags = np.asarray(flags, bool)
        obs_inc = np.zeros(self.num_ranks, np.float64)
        exc_inc = np.zeros(self.num_ranks, np.float64)
        np.add.at(obs_inc, ranks, 1.0)
        np.add.at(exc_inc, ranks, flags.astype(np.float64))
        self._obs *= self._decay
        self._exc *= self._decay
        self._obs += obs_inc
        self._exc += exc_inc
        self.rounds += 1
        self.flagged_total += int(flags.sum())
        self.last_scores[ranks] = scores
        return {"scores": scores, "flags": flags}

    def suspicion(self):
        return self._exc / np.maximum(self._obs, 1e-9)

    def weights_full(self):
        """(num_ranks,) data-plane suspicion weights — exactly 1.0 on a
        clean history (the same identity contract as PlaneDefense)."""
        from . import defense as defense_lib

        return np.asarray(defense_lib.suspicion_weights(
            self.suspicion(), power=self.power, floor=self.floor
        ), np.float32)

    def weights_for(self, ranks):
        w = self.weights_full()[np.asarray(ranks, np.int64)]
        if np.all(w == 1.0):
            return None
        return w.astype(np.float32)

    def stats(self):
        """The summary digest (schema v9 ``summary.data_defense``)."""
        w = self.weights_full()
        return {
            "rounds": int(self.rounds),
            "flagged": int(self.flagged_total),
            "max_score": round(float(self.last_scores.max()), 6),
            "min_w": round(float(w.min()), 6),
        }
