"""GAT's per-relation layer (Velickovic et al., arXiv:1710.10903): a
shared ``w`` on self and neighbours, LeakyReLU(0.2) scores from
``a_self`` and ``a_nbr``, a masked softmax over the sampled neighbours,
and ReLU on the weighted sum. A parent with no live neighbour gets 0."""
import jax.numpy as jnp
import numpy as np


def leaves(d):
    """Glorot-normal ``w``; attention vectors N(0, 0.01)."""
    return {"w": ((d, d), np.sqrt(2.0 / (2 * d))),
            "a_self": ((d,), 0.1), "a_nbr": ((d,), 0.1)}


def forward(p, h_self, h_nbr, mask):
    w = p["w"]
    ws, wn = h_self @ w, h_nbr @ w
    e = (ws * p["a_self"]).sum(-1)[..., None] + (wn * p["a_nbr"]).sum(-1)
    e = jnp.where(e >= 0, e, 0.2 * e)
    e = jnp.where(mask, e, jnp.asarray(-1e9, e.dtype))
    att = jnp.exp(e - e.max(-1, keepdims=True))
    att = att / att.sum(-1, keepdims=True)
    att = jnp.where(mask, att, jnp.zeros((), att.dtype))
    return jnp.maximum((att[..., None] * wn).sum(-2), 0)


def flops(d):
    """(per live (parent, relation) pair, per live child edge): on the pair
    the relation's ``w h_self``, its score and the ReLU; per edge
    ``w h_nbr``, its score, the softmax (5) and the weighted sum."""
    return 2 * d * d + 2 * d + d, 2 * d * d + 2 * d + 5 + 2 * d
