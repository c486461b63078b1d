"""mfu.resident_decode: ``mfu.decode`` in the compressed-resident cells,
where the least time counts the compressed pages and raw tails read (the
same reader). Moves resident_tpot_ms."""

from pathlib import Path

from bench.spec import load_module

read = load_module(Path(__file__).with_name("mfu.decode.py")).read
