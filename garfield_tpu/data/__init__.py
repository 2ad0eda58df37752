"""Deterministic dataset management for Byzantine-resilient SPMD training.

TPU-native counterpart of ``pytorch_impl/libs/garfieldpp/datasets.py`` and
``tensorflow_impl/libs/dataset.py``:

  - ``DataPartitioner`` reproduces the reference's seeded equal-split
    partitioning (datasets.py:121-150, seed 1234 at :124);
  - ``DatasetManager`` serves per-worker train partitions and the global test
    set (datasets.py:152-250), with the reference's "materialize the whole
    loader once" semantics (:243): batch *i* of a run is
    ``train_batches[i % num_batches]``, and any augmentation is sampled once
    at load time, exactly like ``[sample for sample in train_set]``;
  - ``sharded_train_batches`` is the TPU-first addition: the *stacked*
    ``(num_workers, num_batches, bsz, ...)`` array a shard_map program feeds
    from, so per-step batch selection is a static ``lax.dynamic_index`` and
    the host never loops over workers.

Data sources (zero-egress environment — nothing is downloaded):
  1. real files under ``$GARFIELD_TPU_DATA_DIR`` (default ``~/data``):
     MNIST idx/ubyte or ``mnist.npz``; ``cifar-10-batches-py`` pickles;
     ``pima_diabetes.csv``;
  2. otherwise a **deterministic synthetic surrogate** with the same shapes,
     dtypes, class counts and normalization statistics, generated from a
     fixed seed and built to be *learnable* (class-conditional means) so
     convergence tests remain meaningful. A warning is emitted once.
"""

import gzip
import os
import pathlib
import pickle
import struct
import zlib
from random import Random

import numpy as np

from ..utils import tools

__all__ = [
    "datasets_list",
    "Partition",
    "DataPartitioner",
    "DatasetManager",
]

# Reference list (datasets.py:47) + cifar100 (tensorflow_impl tfds names,
# tensorflow_impl/libs/dataset.py:41-87 accepts any tfds dataset) +
# copytask (the synthetic token-sequence task the transformer family
# trains on — no reference counterpart, synthetic BY CONSTRUCTION) +
# synthtokens, synthtokens24k, synthtokens12k, synthtokens25k (seeded token
# sequences labelled with their next token, for the language models of
# models/lfm2.py, mellum.py, laguna.py and phi4flash.py: TOKEN_DATASETS).
datasets_list = [
    "mnist", "cifar10", "cifar100", "pima", "copytask", "synthtokens",
    "synthtokens24k", "synthtokens12k", "synthtokens25k",
]

# Reference normalization constants.
_MNIST_MEAN, _MNIST_STD = 0.1307, 0.3081  # datasets.py:186-187
_CIFAR_MEAN = np.array([0.485, 0.456, 0.406], np.float32)  # datasets.py:198
_CIFAR_STD = np.array([0.229, 0.224, 0.225], np.float32)

_warned_synthetic = set()


def data_dir():
    return pathlib.Path(
        os.environ.get("GARFIELD_TPU_DATA_DIR", str(pathlib.Path.home() / "data"))
    )


# --------------------------------------------------------------------------
# Raw dataset loading: (train_x, train_y), (test_x, test_y) as numpy arrays,
# NHWC float32 images already normalized, int32 labels (float32 (n,1) for
# the binary pima task, mirroring PimaDiabetesDataset targets).
# --------------------------------------------------------------------------


def _synthetic(name, num_classes, shape, n_train, n_test, binary=False):
    """Class-conditional Gaussian surrogate; deterministic and NON-trivial.

    VERDICT r2 #5: the original surrogate (means ~N(0,1) per dim, noise
    0.5) had class centers ~sqrt(2 d) apart — one-shot separable, accuracy
    saturates within a step or two, and every time-to-accuracy threshold
    collapses to the same step. This one overlaps the classes: unit-norm
    mean directions scaled to ``GARFIELD_SURROGATE_MARGIN`` (default 3.5,
    so pairwise center distance is margin*sqrt(2) REGARDLESS of input
    dimension, against unit per-dim noise -> Bayes ceiling ~0.95 for 10
    classes), plus ``GARFIELD_SURROGATE_LABEL_NOISE`` (default 2%) flipped
    labels on the TRAIN split only. A model must now average the signal
    over all input dims and ride out label noise — accuracy climbs over
    hundreds of SGD steps and t(acc>=0.5) << t(acc>=0.9), which is what
    the robust-aggregation TTA tables need (reference anchor: real
    CIFAR-10 runs, Aggregathor/run_exp.sh:5-14).
    """
    if name not in _warned_synthetic:
        tools.warning(
            f"dataset {name!r} not found under {data_dir()} — using the "
            "deterministic synthetic surrogate (same shapes/classes)"
        )
        _warned_synthetic.add(name)
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    dim = int(np.prod(shape))
    margin = float(os.environ.get("GARFIELD_SURROGATE_MARGIN", "3.5"))
    label_noise = float(
        os.environ.get("GARFIELD_SURROGATE_LABEL_NOISE", "0.02")
    )
    # Image-shaped tasks get SPATIALLY SMOOTH class means (a low-res
    # pattern upsampled to full resolution): a random per-pixel direction
    # is invisible to a convnet's translation-local inductive bias (probed:
    # accuracy pinned at chance), while low-frequency patterns are exactly
    # what conv stacks extract — like real image class structure.
    if len(shape) == 3:
        h, w, c = shape
        lo = rng.normal(
            0.0, 1.0,
            size=(num_classes, max(h // 4, 1), max(w // 4, 1), c),
        ).astype(np.float32)
        means = np.stack([
            np.repeat(
                np.repeat(m, -(-h // m.shape[0]), axis=0)[:h],
                -(-w // m.shape[1]), axis=1,
            )[:, :w]
            for m in lo
        ]).reshape(num_classes, dim)
    else:
        means = rng.normal(
            0.0, 1.0, size=(num_classes, dim)
        ).astype(np.float32)
    means *= margin / np.linalg.norm(means, axis=1, keepdims=True)

    def make(n, seed, train):
        r = np.random.default_rng(seed)
        y = r.integers(0, num_classes, size=n)
        x = means[y] + r.normal(size=(n, dim)).astype(np.float32)
        x = x.reshape((n,) + shape).astype(np.float32)
        if train and label_noise:
            flip = r.random(n) < label_noise
            y = np.where(
                flip, r.integers(0, num_classes, size=n), y
            )
        if binary:
            return x.reshape(n, -1), y.astype(np.float32).reshape(-1, 1)
        return x, y.astype(np.int32)

    return make(n_train, 1234, True), make(n_test, 4321, False)


def _load_mnist_files(root):
    """MNIST from idx-ubyte (possibly .gz) or mnist.npz under root."""
    npz = root / "mnist.npz"
    if npz.exists():
        with np.load(npz) as z:
            return (z["x_train"], z["y_train"]), (z["x_test"], z["y_test"])

    def read_idx(path):
        opener = gzip.open if path.suffix == ".gz" else open
        with opener(path, "rb") as fh:
            magic, = struct.unpack(">I", fh.read(4))
            ndim = magic & 0xFF
            dims = struct.unpack(">" + "I" * ndim, fh.read(4 * ndim))
            return np.frombuffer(fh.read(), dtype=np.uint8).reshape(dims)

    def find(stem):
        for cand in (root / "MNIST" / "raw", root):
            for suffix in ("", ".gz"):
                p = cand / (stem + suffix)
                if p.exists():
                    return read_idx(p)
        raise FileNotFoundError(stem)

    return (
        (find("train-images-idx3-ubyte"), find("train-labels-idx1-ubyte")),
        (find("t10k-images-idx3-ubyte"), find("t10k-labels-idx1-ubyte")),
    )


def load_mnist():
    try:
        (tx, ty), (vx, vy) = _load_mnist_files(data_dir())
    except (FileNotFoundError, OSError):
        return _synthetic("mnist", 10, (28, 28, 1), 60000, 10000)
    norm = lambda x: (
        (x.astype(np.float32) / 255.0 - _MNIST_MEAN) / _MNIST_STD
    ).reshape(-1, 28, 28, 1)
    return (norm(tx), ty.astype(np.int32)), (norm(vx), vy.astype(np.int32))


def _load_cifar_files(root, name):
    if name == "cifar10":
        d = root / "cifar-10-batches-py"
        train_files = [d / f"data_batch_{i}" for i in range(1, 6)]
        test_files = [d / "test_batch"]
        label_key = b"labels"
    else:
        d = root / "cifar-100-python"
        train_files, test_files = [d / "train"], [d / "test"]
        label_key = b"fine_labels"

    def load(files):
        xs, ys = [], []
        for f in files:
            with open(f, "rb") as fh:
                batch = pickle.load(fh, encoding="bytes")
            xs.append(batch[b"data"])
            ys.extend(batch[label_key])
        x = np.concatenate(xs).reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)
        return x, np.asarray(ys, np.int32)

    return load(train_files), load(test_files)


def _augment_once(x, seed):
    """Random crop (pad 4) + horizontal flip, sampled once per sample at load
    time — matching the reference's materialize-once loader (datasets.py:197-
    201, :243)."""
    rng = np.random.default_rng(seed)
    n, h, w, c = x.shape
    padded = np.pad(x, ((0, 0), (4, 4), (4, 4), (0, 0)), mode="constant")
    ys = rng.integers(0, 9, size=n)
    xs = rng.integers(0, 9, size=n)
    flip = rng.random(n) < 0.5
    out = np.empty_like(x)
    for i in range(n):
        crop = padded[i, ys[i] : ys[i] + h, xs[i] : xs[i] + w]
        out[i] = crop[:, ::-1] if flip[i] else crop
    return out


def load_cifar(name="cifar10", augment_train=True):
    num_classes = 10 if name == "cifar10" else 100
    try:
        (tx, ty), (vx, vy) = _load_cifar_files(data_dir(), name)
    except (FileNotFoundError, OSError):
        return _synthetic(name, num_classes, (32, 32, 3), 50000, 10000)
    norm = lambda x: (x.astype(np.float32) / 255.0 - _CIFAR_MEAN) / _CIFAR_STD
    tx = norm(tx)
    if augment_train:
        tx = _augment_once(tx, seed=1234)
    return (tx, ty), (norm(vx), vy)


def load_pima(train_size=None):
    """Pima Indians Diabetes (datasets.py:52-94): 600 train / last 168 test,
    z-scored features computed on the served split, float32 (n,1) targets."""
    csv = data_dir() / "pima_diabetes.csv"
    if not csv.exists():
        (tx, ty), (vx, vy) = _synthetic(
            "pima", 2, (8,), 600, 168, binary=True
        )
        if train_size is not None:
            tx, ty = tx[:train_size], ty[:train_size]
        return (tx, ty), (vx, vy)
    raw = np.genfromtxt(csv, delimiter=",", skip_header=1, dtype=np.float64)

    def split(rows):
        data, targets = rows[:, :-1], rows[:, -1]
        data = data - data.mean(axis=0)
        data = data / data.std(axis=0, ddof=1)
        return data.astype(np.float32), targets.astype(np.float32).reshape(-1, 1)

    train_split = 600 if train_size is None else min(600, train_size)
    return split(raw[:train_split]), split(raw[-168:])


COPYTASK_SEQ = 16
COPYTASK_VOCAB = 32
COPYTASK_CLASSES = 10


def load_copytask(train_size=None):
    """Synthetic marked-copy sequence task (the transformer workload).

    Each sample is an int32 token sequence of length ``COPYTASK_SEQ``
    over a ``COPYTASK_VOCAB``-token vocabulary: distractor tokens
    everywhere except one MARKER token (the last vocab id) at a random
    position, immediately followed by a payload token in
    ``[0, COPYTASK_CLASSES)`` — the label. A model must ATTEND to the
    marked position to classify (payload ids never appear in distractor
    slots, but the marker's position is uniform, so no fixed-position
    readout works) — accuracy climbs over SGD steps instead of
    saturating at once, which the robust-aggregation TTA rows need
    (the same non-triviality contract as ``_synthetic``, VERDICT r2
    #5). Unlike the image surrogates this is not a stand-in for absent
    real files: the task is synthetic by construction (no network
    fetch, no warning). Train labels carry the standard
    ``GARFIELD_SURROGATE_LABEL_NOISE`` flips; seeds follow the
    ``_synthetic`` discipline (train 1234 / test 4321).
    """
    T, C = COPYTASK_SEQ, COPYTASK_CLASSES
    marker = COPYTASK_VOCAB - 1
    label_noise = float(
        os.environ.get("GARFIELD_SURROGATE_LABEL_NOISE", "0.02")
    )

    def make(n, seed, train):
        r = np.random.default_rng(seed)
        x = r.integers(C, marker, size=(n, T))
        pos = r.integers(0, T - 1, size=n)
        y = r.integers(0, C, size=n)
        x[np.arange(n), pos] = marker
        x[np.arange(n), pos + 1] = y
        if train and label_noise:
            flip = r.random(n) < label_noise
            y = np.where(flip, r.integers(0, C, size=n), y)
        return x.astype(np.int32), y.astype(np.int32)

    tx, ty = make(8192, 1234, True)
    if train_size is not None:
        tx, ty = tx[:train_size], ty[:train_size]
    return (tx, ty), make(2048, 4321, False)


SYNTHTOKENS_VOCAB = 16384
SYNTHTOKENS_SEQ = 2048
# The token datasets: ``{name: (vocabulary slice, sequence length)}``, each
# the slice and the length one language-model preset is benchmarked at
# (``lfm2_8b_a1b_ep4``; ``mellum2_12b_a2p5b_ep4``; ``laguna_xs2_ep16``;
# ``phi4_mini_flash_l15_19``). The slice is also
# ``models.num_classes_dict[name]``.
TOKEN_DATASETS = {
    "synthtokens": (SYNTHTOKENS_VOCAB, SYNTHTOKENS_SEQ),
    "synthtokens24k": (24576, 4096),
    "synthtokens12k": (12544, 4096),
    "synthtokens25k": (25008, 4096),
}


def load_synthtokens(train_size=None, name="synthtokens"):
    """Seeded token sequences labelled with the next token at every
    position (the ``next-token`` loss; models/lfm2.py, models/mellum.py,
    models/laguna.py, models/phi4flash.py).

    x is (N, length) int32 below the vocabulary slice, both
    ``TOKEN_DATASETS[name]``'s; y is x moved one place on. The law is
    `tokens.sequences`'s — a token repeats the one two places back or is a
    fresh Zipf-Mandelbrot draw — and the benchmark's cells draw from the
    same (benchmark/inputs/next_tokens.py). Synthetic by construction, like
    copytask: no file is read, no warning.
    """
    import jax

    from . import tokens

    vocab, length = TOKEN_DATASETS[name]

    def make(n, seed):
        made = np.asarray(tokens.sequences(
            jax.random.PRNGKey(seed), (n,), length + 2, vocab))
        return made[:, :length], made[:, 1:length + 1]

    tx, ty = make(1024, 1234)
    if train_size is not None:
        tx, ty = tx[:train_size], ty[:train_size]
    return (tx, ty), make(128, 4321)


def load_dataset(name, train_size=None):
    if name == "mnist":
        return load_mnist()
    if name in ("cifar10", "cifar100"):
        return load_cifar(name)
    if name == "pima":
        return load_pima(train_size)
    if name == "copytask":
        return load_copytask(train_size)
    if name in TOKEN_DATASETS:
        return load_synthtokens(train_size, name)
    raise ValueError(f"Existing datasets are: {datasets_list}")


# --------------------------------------------------------------------------
# Partitioning (datasets.py:97-150)
# --------------------------------------------------------------------------


class Partition:
    """Index-view over a dataset (datasets.py:97-118)."""

    def __init__(self, data, index):
        self.data = data
        self.index = np.asarray(index, dtype=np.int64)

    def __len__(self):
        return len(self.index)

    def __getitem__(self, i):
        return self.data[self.index[i]]

    def take(self, arrays):
        """Gather this partition's rows from each array in ``arrays``."""
        return tuple(a[self.index] for a in arrays)


class DataPartitioner:
    """Seeded equal-split partitioner, bit-compatible with the reference
    (datasets.py:121-150): a single ``random.Random(seed)`` stream shuffles
    each successive leading slice of the remaining indices, so partitions are
    disjoint and deterministic given (len, sizes, seed)."""

    def __init__(self, data_len, sizes, seed=1234):
        self.partitions = []
        rng = Random()
        rng.seed(seed)
        indexes = list(range(data_len))
        for frac in sizes:
            part_len = int(frac * data_len)
            tmp = indexes[0:part_len]
            rng.shuffle(tmp)
            self.partitions.append(tmp)
            indexes = indexes[part_len:]

    def use(self, partition):
        return np.asarray(self.partitions[partition], dtype=np.int64)


# --------------------------------------------------------------------------
# Manager (datasets.py:152-250)
# --------------------------------------------------------------------------


def _batchify(x, y, bsz):
    """Split into full batches, dropping the tail remainder like a DataLoader
    list comprehension would keep it — the reference keeps a ragged final
    batch; for XLA static shapes we drop it (documented deviation; at most
    bsz-1 samples per epoch)."""
    n = (len(x) // bsz) * bsz
    xb = x[:n].reshape((-1, bsz) + x.shape[1:])
    yb = y[:n].reshape((-1, bsz) + y.shape[1:])
    return xb, yb


class DatasetManager:
    """Per-node dataset view (datasets.py:152-250).

    ``rank`` / ``size`` / ``num_workers`` follow the reference convention:
    ranks [0, num_ps) are parameter servers, workers hold partition
    ``rank - num_ps`` (:232-243). ``minibatch`` is the per-worker batch size
    (the reference stores batch = minibatch*num_workers then divides back,
    :166, :235-236).
    """

    def __init__(self, dataset, minibatch, num_workers, size, rank, train_size=None):
        if dataset not in datasets_list:
            raise ValueError(f"Existing datasets are: {datasets_list}")
        self.dataset = dataset
        self.minibatch = int(minibatch)
        self.num_workers = int(num_workers)
        self.num_ps = int(size) - int(num_workers)
        self.rank = int(rank)
        self.train_size = train_size
        self._train = None
        self._test = None

    def _load(self):
        if self._train is None:
            self._train, self._test = load_dataset(self.dataset, self.train_size)
        return self._train, self._test

    def worker_index(self, rank=None):
        r = self.rank if rank is None else rank
        return r - self.num_ps

    def get_train_set(self, rank=None):
        """This worker's batches as (num_batches, bsz, ...) arrays; batch i of
        a training run is index ``i % num_batches`` (datasets.py:232-243)."""
        (tx, ty), _ = self._load()
        sizes = [1.0 / self.num_workers] * self.num_workers
        part = DataPartitioner(len(tx), sizes)
        idx = part.use(self.worker_index(rank))
        return _batchify(tx[idx], ty[idx], self.minibatch)

    def sharded_train_batches(self):
        """All workers' batch streams stacked: (W, B, bsz, ...) — the array a
        shard_map program shards over the "workers" mesh axis. TPU-first
        replacement for per-rank DataLoaders."""
        xs, ys = [], []
        for w in range(self.num_workers):
            xb, yb = self.get_train_set(rank=self.num_ps + w)
            xs.append(xb)
            ys.append(yb)
        nb = min(x.shape[0] for x in xs)
        return (
            np.stack([x[:nb] for x in xs]),
            np.stack([y[:nb] for y in ys]),
        )

    def get_test_set(self, batch=100):
        """Global test set, batched at 100 like the reference loader
        (datasets.py:245-250). Returns a list of (x, y) batches; the final
        batch may be smaller (the reference DataLoader keeps the ragged tail
        — dropping it would, e.g., discard 68 of pima's 168 test samples)."""
        _, (vx, vy) = self._load()
        return [
            (vx[i : i + batch], vy[i : i + batch])
            for i in range(0, len(vx), batch)
        ]
