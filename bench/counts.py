"""The chip's peaks, and the bytes and operations of each measured kernel.

Kernel counts are what the algorithm needs for the call, worked out from its
shapes, never read from the program: a kernel whose time grows for work the
algorithm does not need shows a lower share of its roofline.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable, Tuple

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """Peak rates of one chip of ``device_kind``; KeyError if unknown."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"device {device_kind!r} is not in {PEAKS_FILE.name} "
                       f"(known: {sorted(table)})")
    return table[device_kind]


def roofline_s(flops: float, nbytes: float, peak: Dict[str, float]
               ) -> Tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


# -- the SplitZip codec (bf16 container, per-chunk escape lists) ------------

def escape_bytes_per_chunk(cap: int) -> int:
    """Per chunk: ``cap`` escape slots of a u16 position and a u8 value,
    and the i32 count."""
    return 3 * cap + 4


def encode_bytes(n_elems: int, *, chunk: int, cap: int) -> float:
    """HBM bytes of a fused encode of ``n_elems`` bf16 elements: the 2-byte
    input read; the sign-mantissa byte, the packed exponent nibble and the
    escape lists written."""
    n_chunks = -(-n_elems // chunk)
    return n_chunks * chunk * (2 + 1 + 0.5) \
        + n_chunks * escape_bytes_per_chunk(cap)


def decode_bytes(n_elems: int, *, chunk: int, cap: int) -> float:
    """HBM bytes of a fused decode: the streams read, the bf16 bits
    written (the same total as the encode)."""
    return encode_bytes(n_elems, chunk=chunk, cap=cap)


# -- the paged attention kernel over compressed pages -----------------------

def page_bytes(page_elems: int, escape_cap: int) -> int:
    """HBM bytes of one compressed page: 1.5 B an element, the page's
    escape slots (u16 position, u8 value) and its count."""
    return page_elems + page_elems // 2 + 3 * escape_cap + 4


def paged_attention_step(*, rows: int, heads: int, head_dim: int,
                         dv: int, full_pages: Iterable[int],
                         tokens_per_page: int, page_bytes_kv: int
                         ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one call of a paged attention kernel, one layer.

    Each query head scores ``head_dim``-wide queries against every token of
    the row's pages and sums ``dv``-wide values. ``full_pages`` holds each
    row's compressed pages in use; the kernel reads each such page of every
    paged leaf once (``page_bytes_kv`` for the set: K and V, or the latent
    and its rope key), the bf16 queries, and writes f32 partials (acc, max,
    sum)."""
    pages = sum(full_pages)
    tokens = pages * tokens_per_page
    flops = 2.0 * heads * (head_dim + dv) * tokens
    nbytes = pages * page_bytes_kv + rows * heads * head_dim * 2 \
        + rows * heads * (dv + 2) * 4
    return flops, nbytes
