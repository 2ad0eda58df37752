"""Loss / optimizer selectors (optax-based).

Counterpart of pytorch_impl/libs/garfieldpp/tools.py: select_loss (:47-57,
nll/cross-entropy/bce), select_optimizer (:107-123, sgd/adam/adamw/rmsprop/
adagrad) and adjust_learning_rate (:165-172, lr *= 0.2 scheduling).
"""

import jax
import jax.flatten_util
import jax.numpy as jnp
import optax


def select_loss(name):
    """Return ``loss_fn(outputs, labels) -> scalar`` by name.

    Supported: ``nll`` (expects log-probabilities), ``cross-entropy`` /
    ``crossentropy`` (expects raw logits), ``bce`` / ``binary-cross-entropy``
    (expects a *probability* per example like torch nn.BCELoss — the pima
    model ends in sigmoid), ``bce-logits`` / ``bce-with-logits`` (expects a
    single raw logit per example), ``next-token`` (raw logits (batch,
    time, vocabulary) against the next token at every position (batch,
    time): the mean softmax cross-entropy over all positions, in float32).
    """
    name = name.lower()
    if name == "nll":
        def nll(log_probs, labels):
            return -jnp.mean(
                jnp.take_along_axis(log_probs, labels[:, None], axis=-1)
            )
        return nll
    if name in ("cross-entropy", "crossentropy", "ce"):
        def ce(logits, labels):
            return jnp.mean(
                optax.softmax_cross_entropy_with_integer_labels(logits, labels)
            )
        return ce
    if name in ("bce", "binary-cross-entropy"):
        # torch nn.BCELoss (tools.py:55) expects *probabilities* (the pima
        # model ends in sigmoid, models/pimanet.py) — not logits.
        def bce(probs, labels):
            p = jnp.clip(probs.reshape(labels.shape), 1e-7, 1.0 - 1e-7)
            labels = labels.astype(p.dtype)
            return -jnp.mean(labels * jnp.log(p) + (1.0 - labels) * jnp.log1p(-p))
        return bce
    if name in ("bce-logits", "bce-with-logits"):
        def bce_logits(logits, labels):
            logits = logits.reshape(labels.shape)
            return jnp.mean(
                optax.sigmoid_binary_cross_entropy(logits, labels.astype(logits.dtype))
            )
        return bce_logits
    if name in ("next-token", "next_token"):
        def next_token(logits, labels):
            return jnp.mean(optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), labels
            ))
        return next_token
    raise ValueError(
        f"unknown loss {name!r}; available: nll, cross-entropy, bce, "
        f"bce-logits, next-token"
    )


def select_optimizer(name, *, lr, momentum=0.0, weight_decay=0.0, **kwargs):
    """Return an ``optax.GradientTransformation`` by name.

    Mirrors the reference's optimizer table (garfieldpp/tools.py:107-123):
    sgd / adam / adamw / rmsprop / adagrad, with the reference CLI's JSON
    optimizer-args (lr, momentum, weight_decay) accepted uniformly.
    """
    name = name.lower()
    if callable(lr):
        schedule = lr
    else:
        schedule = optax.constant_schedule(float(lr))
    if name == "sgd":
        tx = optax.sgd(schedule, momentum=momentum or None)
    elif name == "adam":
        tx = optax.adam(schedule, **kwargs)
    elif name == "adamw":
        tx = optax.adamw(schedule, weight_decay=weight_decay, **kwargs)
        weight_decay = 0.0  # already applied decoupled
    elif name == "rmsprop":
        tx = optax.rmsprop(schedule, momentum=momentum, **kwargs)
    elif name == "adagrad":
        tx = optax.adagrad(schedule, **kwargs)
    else:
        raise ValueError(
            f"unknown optimizer {name!r}; available: sgd, adam, adamw, rmsprop, adagrad"
        )
    if weight_decay and name != "adamw":
        # Reference applies L2 via the optimizer's weight_decay argument
        # (coupled decay) — optax equivalent is additive decay before update.
        tx = optax.chain(optax.add_decayed_weights(weight_decay), tx)
    return tx


def adjust_learning_rate(base_lr, *, decay=0.2, every_epochs=30, iters_per_epoch=1):
    """Step-decay schedule: lr = base_lr * decay^(epoch // every_epochs).

    Counterpart of garfieldpp/tools.py:165-172 and the AggregaThor trainer's
    epoch decay (Aggregathor/trainer.py:227-229, x0.2 every 30 epochs).
    Returns an optax schedule over *iteration* count.
    """
    def schedule(step):
        epoch = step // iters_per_epoch
        return base_lr * (decay ** (epoch // every_epochs))
    return schedule


def tree_flatten_1d(tree):
    """Flatten a pytree of arrays into one 1-D vector plus an unflattener.

    The reference flattens all parameter gradients into a single 1-D tensor
    before shipping them (worker.py:93-94, tools/pytorch.py:27-64 `flatten`);
    GARs operate on those flat vectors. This is the jax equivalent.
    """
    flat, unravel = jax.flatten_util.ravel_pytree(tree)
    return flat, unravel
