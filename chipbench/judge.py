"""The comparison that decides ``correct``: how far each served greedy
token lies below the reference's best choice, in logits.

For a greedy token ``t`` at a position with reference logits ``z`` the gap
is ``max(z) - z[t]``: zero when the reference puts ``t`` first, and small
where the program's rounding flips a near-tie.  Sampled tokens are not
compared: a draw has no single right answer to hold it to.  The control
(the reference in a lower precision) is scored the same way, by the gap
of the token it puts first at each position.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ROW_PAD = 64          # positions are padded to a power-of-two multiple


@jax.jit
def _gaps(z, tok):
    return z.max(-1) - jnp.take_along_axis(z, tok[:, None], axis=-1)[:, 0]


def _pad(n: int) -> int:
    """Rows padded to ROW_PAD times a power of two (few shapes compile)."""
    return ROW_PAD * 2 ** int(np.ceil(np.log2(-(-n // ROW_PAD))))


def served_gaps(ref_logits, tokens: np.ndarray) -> np.ndarray:
    """Gap of each served greedy token under the reference logits (rows
    padded, as :func:`rows` gives them)."""
    n = tokens.shape[0]
    tok = np.zeros(_pad(n), np.int32)
    tok[:n] = tokens
    return np.asarray(_gaps(ref_logits, jnp.asarray(tok)))[:n]


def control_gaps(ref_logits, control_logits, n: int) -> np.ndarray:
    """Gap of the token the control puts first at each position, scored by
    the reference."""
    chosen = jnp.argmax(control_logits, -1).astype(jnp.int32)
    return np.asarray(_gaps(ref_logits, chosen))[:n]


def rows(prompt_len: int, n_served: int) -> np.ndarray:
    """Positions whose logits predict the served tokens, padded."""
    r = prompt_len - 1 + np.arange(_pad(n_served))
    return np.minimum(r, prompt_len + n_served - 2).astype(np.int32)
