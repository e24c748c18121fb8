"""The uniform relation mix of Graph4Rec Eq. 3 (phi_r = 1/R): the mean of
the per-relation outputs. It has no leaves of its own."""


def leaves(d):
    return {}


def forward(p, rel):
    return sum(rel) / len(rel)


def flops(d):
    """Per parent: none needed. A bipartite graph leaves one relation live
    per ego node, and its 1/R folds into the residual's (1 - alpha)."""
    return 0
