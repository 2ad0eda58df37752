"""SGD with momentum and coupled weight decay (optax ``trace`` after
``add_decayed_weights``): after the first step the momentum trace is
g + weight_decay * start, so g is the trace less that."""

import jax


def first_gradient(opt_state, start, opt):
    import optax

    if not opt["momentum"]:
        raise ValueError("plain SGD keeps no trace to read the gradient from")
    found = [
        leaf for leaf in jax.tree.leaves(
            opt_state, is_leaf=lambda s: isinstance(s, optax.TraceState))
        if isinstance(leaf, optax.TraceState)
    ]
    if len(found) != 1:
        raise ValueError("expected one momentum trace in the optimizer")
    return jax.tree.map(
        lambda m, p: m - opt["weight_decay"] * p, found[0].trace, start)
