"""LightGCN's per-relation layer (He et al., arXiv:2002.02126): the masked
mean of a relation's sampled neighbours, with no transform and no
nonlinearity. It has no leaves of its own."""
import jax.numpy as jnp


def leaves(d):
    return {}


def forward(p, h_self, h_nbr, mask):
    m = mask[..., None].astype(h_nbr.dtype)
    return (h_nbr * m).sum(-2) / jnp.maximum(m.sum(-2), 1)


def flops(d):
    """(per live (parent, relation) pair, per live child edge): one divide
    per dim; one add per dim."""
    return d, d
