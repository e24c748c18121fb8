"""The uniform relation mix of Graph4Rec Eq. 3 (phi_r = 1/R): the mean of
the per-relation outputs. It has no leaves of its own."""


def leaves(d):
    return {}


def forward(p, rel):
    return sum(rel) / len(rel)


def flops(d, n):
    """Per live parent mixing ``n`` >= 1 live relations: the n - 1 adds of
    their sum (a dead relation's output is 0); the 1/R folds into the
    residual's (1 - alpha)."""
    return (n - 1) * d
