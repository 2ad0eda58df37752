"""SGD with momentum and coupled weight decay, by hand (torch.optim.SGD):
m <- g + weight_decay * p + momentum * m;  p <- p - lr * m."""

import jax.numpy as jnp


def init(params):
    return {path: jnp.zeros_like(p) for path, p in params.items()}


def update(params, momentum, grad, opt):
    momentum = {
        p: grad[p] + opt["weight_decay"] * params[p]
        + opt["momentum"] * momentum[p] for p in params
    }
    params = {p: params[p] - opt["lr"] * momentum[p] for p in params}
    return params, momentum
