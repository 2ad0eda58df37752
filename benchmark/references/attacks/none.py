"""No attack: every worker submits its own gradient."""

# A leaf's result needs that leaf's rows alone (`harness/reference.py`).
LEAFWISE = True


def byzantine(n, f):
    """No row is replaced."""
    return [False] * n


def apply(stack, byz):
    return stack
