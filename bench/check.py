"""The comparison that decides ``correct``.

Two numbers, each with its limit from ``bench/limits/<cell>.json``:

* ``logit_gap``: for a sample of the requests served in the window, drawn
  from the seed, the reference runs once over each prompt followed by its
  served tokens. At each served position the gap is the reference's best
  logit less the reference's logit of the token the program served (0 where
  they agree). The number is the widest gap: greedy decoding that drifts,
  a prefill or a decode step that computes something else, or a token
  altered on its way out all widen it.
* ``transfer_bits``: for one batch of the window, drawn from the seed, the
  16-bit words of the cache the decode side holds that differ from the cache
  prefill sent (a resident pool decoded back first). The transfer is
  lossless by design, so the limit is 0.

The control (``control_gap``, read by ``bench/calibrate.py`` and never in a
benchmark run) is the same reference with every product in float8: at each
position, the gap of the token the float8 logits put first.
"""

from __future__ import annotations

from typing import Dict, Iterable, List

import jax
import jax.numpy as jnp
import numpy as np


def sample_requests(seed: int, attempted: int, want: int) -> List[int]:
    """Request indices to compare, drawn from the seed (all the same
    length in a closed loop, so each is among the longest)."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
    want = min(want, attempted)
    return sorted(rng.choice(attempted, size=want, replace=False).tolist())


def gaps(logits: np.ndarray, served: np.ndarray) -> np.ndarray:
    """Best logit less the served token's logit; (rows, positions)."""
    got = np.take_along_axis(logits, served[..., None], axis=-1)[..., 0]
    return logits.max(axis=-1) - got


def control_gaps(ref: np.ndarray, low: np.ndarray) -> np.ndarray:
    """The reference's gap of the token the lower precision puts first."""
    return gaps(ref, low.argmax(axis=-1))


def differing_words(sent, received) -> int:
    """16-bit (or 8/32-bit) words that differ between two pytrees."""
    a, b = jax.tree.leaves(sent), jax.tree.leaves(received)
    if len(a) != len(b):
        return -1

    def bits(x):
        return jax.lax.bitcast_convert_type(
            x, {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize])
    total = 0
    for x, y in zip(a, b):
        if x.shape != y.shape or x.dtype != y.dtype:
            return -1
        total += int(jnp.sum(bits(x) != bits(y)))
    return total


def verdict(readings: Dict[str, float], limits: dict) -> Dict[str, dict]:
    """Each number beside its limit; a reading of -1 or NaN fails."""
    out = {}
    for name, value in readings.items():
        lim = float(limits[name]["limit"])
        ok = value == value and value >= 0 and value <= lim
        out[name] = {"value": value, "limit": lim, "ok": bool(ok)}
    return out


def all_ok(checks: Dict[str, dict]) -> bool:
    return all(c["ok"] for c in checks.values())


def lines(checks: Dict[str, dict]) -> Iterable[str]:
    for name, c in checks.items():
        yield (f"check {name}: {c['value']!r} limit {c['limit']!r} "
               f"{'ok' if c['ok'] else 'FAILED'}")
