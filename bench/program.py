"""The system under test, built as its own entry points build it.

This file and the family files (``bench/families/<family>.py``, which map a
configuration file onto the program's ``ArchConfig``) are the only files of
the benchmark that import the program (``repro``, from ``src/``). It draws
the weights on the device in one jitted call of the program's
``init_params``, calibrates the codebook as ``launch/serve.py`` does, and
builds one ``DisaggregatedEngine`` whose three stages the harness drives.
"""

from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

import jax

from bench import seeds, spec

ROOT = Path(__file__).resolve().parents[1]
SRC = ROOT / "src"


def _importable() -> None:
    """Put the checkout's ``src/`` on the import path (once)."""
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def arch_config(conf: dict, root: Path = ROOT):
    """The program's ``ArchConfig`` for a configuration file: the mapping of
    its family, ``<root>/bench/families/<family>.py``."""
    _importable()
    path = root / "bench" / "families" / f"{conf['family']}.py"
    if not path.is_file():
        raise ValueError(f"family {conf['family']!r}: no file {path}")
    return spec.load_module(path).arch_config(conf)


class Served:
    """One engine serving one cell's traffic, and what the check needs."""

    def __init__(self, conf: dict, mix: dict, seed: int, root: Path = ROOT):
        _importable()
        from repro.launch.serve import calibrate_on_model
        from repro.models import model as M
        from repro.serving.engine import DisaggregatedEngine

        self.cfg = arch_config(conf, root)
        cfg = self.cfg
        self.params = jax.block_until_ready(jax.jit(
            lambda k: M.init_params(cfg, k))(seeds.key(seed, "weights")))
        self.codebook = calibrate_on_model(cfg, self.params)
        self.resident = mix["resident"] == "compressed"
        self.engine = DisaggregatedEngine(
            cfg, self.params, self.codebook, backend="auto",
            compress=mix["transfer"] == "compressed",
            resident=mix["resident"])
        self.new_tokens = int(mix["new_tokens"])
        self.max_seq = int(mix["prompt_tokens"]) + 1 + self.new_tokens
        if self.resident:
            tp = self.engine.resident_tokens_per_page()
            self.max_seq = -(-self.max_seq // tp) * tp

    @property
    def backend(self) -> str:
        return self.engine.tc.get_backend().name

    def prefill(self, batch):
        return self.engine.prefill(batch, max_seq=self.max_seq)

    def transfer(self, state):
        return self.engine.transfer(state)

    def decode(self, first_token, state):
        return self.engine.decode(first_token, state, self.new_tokens)

    def received_cache(self, state):
        """The raw cache the decode side holds: a resident state's pages
        decoded back (bit-exact by the pool's contract)."""
        from repro.models.kvpool import KVPool, ResidentState
        if not isinstance(state, ResidentState):
            return state.cache
        pool = KVPool(state.geom, self.engine.tc.get_backend(),
                      self.codebook)
        return pool.rehydrate(state)

    def counters(self) -> dict:
        """The engine's counters (``EngineStats``), scalars only."""
        return {k: v for k, v in dataclasses.asdict(self.engine.stats).items()
                if isinstance(v, (int, float, bool))}
