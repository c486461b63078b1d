"""The system under test, built as its own entry points build it.

This is the only file of the benchmark that imports the program (``repro``,
from ``src/``). It maps a configuration file onto the program's
``ArchConfig``, draws the weights on the device in one jitted call of the
program's ``init_params``, calibrates the codebook as ``launch/serve.py``
does, and builds one ``DisaggregatedEngine`` whose three stages the harness
drives.
"""

from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

import jax

from bench import seeds

SRC = Path(__file__).resolve().parents[1] / "src"


def _importable() -> None:
    """Put the checkout's ``src/`` on the import path (once)."""
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for a configuration file."""
    _importable()
    from repro.configs.base import ArchConfig, MLAConfig
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    kw = dict(name=conf.get("model_type", "model"), family="dense",
              num_layers=conf["num_hidden_layers"], d_model=d, num_heads=h,
              num_kv_heads=conf["num_key_value_heads"],
              d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
              rope_theta=conf["rope_theta"], norm_eps=conf["rms_norm_eps"],
              tie_embeddings=bool(conf.get("tie_word_embeddings", False)))
    if conf["family"] == "gqa":
        kw["head_dim"] = conf["head_dim"]
    elif conf["family"] == "mla":
        kw["head_dim"] = d // h
        kw["mla"] = MLAConfig(
            q_lora_rank=conf["q_lora_rank"],
            kv_lora_rank=conf["kv_lora_rank"],
            qk_nope_head_dim=conf["qk_nope_head_dim"],
            qk_rope_head_dim=conf["qk_rope_head_dim"], v_head_dim=d // h)
    else:
        raise ValueError(f"family {conf['family']!r}: known gqa, mla")
    return ArchConfig(**kw)


class Served:
    """One engine serving one cell's traffic, and what the check needs."""

    def __init__(self, conf: dict, mix: dict, seed: int):
        _importable()
        from repro.launch.serve import calibrate_on_model
        from repro.models import model as M
        from repro.serving.engine import DisaggregatedEngine

        self.cfg = arch_config(conf)
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
