"""The program's configuration of each family, found by the configuration's
``family`` in ``bench/families/<family>.py``.

Both configuration files must build, field for field, the ``ArchConfig``
the benchmark has run since it was first measured (written out here), so
that no cell runs another model; a family with no file is an error that
names the file; a family file added to a checkout is found with no edit to
an existing file.
"""

import json
import shutil
from pathlib import Path

import pytest

from bench import program

REPO = Path(__file__).resolve().parents[2]
EXPECTED = {
    "qwen3-32b-l8": dict(
        name="qwen3", family="dense", num_layers=8, d_model=5120,
        num_heads=64, num_kv_heads=8, d_ff=25600, vocab_size=151936,
        head_dim=128, rope_theta=1000000.0, norm_eps=1e-6,
        tie_embeddings=False, mla=None),
    "minicpm3-4b": dict(
        name="minicpm3", family="dense", num_layers=62, d_model=2560,
        num_heads=40, num_kv_heads=40, d_ff=6400, vocab_size=73448,
        head_dim=64, rope_theta=10000.0, norm_eps=1e-5,
        tie_embeddings=False,
        mla=dict(q_lora_rank=768, kv_lora_rank=256, qk_nope_head_dim=64,
                 qk_rope_head_dim=32, v_head_dim=64)),
}


def _conf(name):
    return json.loads((REPO / "bench" / "configs" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_config_builds_the_same_arch_config(name):
    got = program.arch_config(_conf(name))         # puts src/ on the path
    from repro.configs.base import ArchConfig, MLAConfig
    want = dict(EXPECTED[name])
    if want["mla"] is not None:
        want["mla"] = MLAConfig(**want["mla"])
    assert got == ArchConfig(**want)


def test_unknown_family_names_the_file_it_looked_for():
    conf = dict(_conf("qwen3-32b-l8"), family="moe")
    with pytest.raises(ValueError, match=r"bench/families/moe\.py"):
        program.arch_config(conf)


def test_new_family_is_a_new_file(tmp_path):
    shutil.copytree(REPO / "bench" / "families",
                    tmp_path / "bench" / "families")
    (tmp_path / "bench" / "families" / "tiny.py").write_text(
        "from repro.configs.base import ArchConfig\n\n\n"
        "def arch_config(conf):\n"
        "    return ArchConfig(name='tiny', family='dense', num_layers=1,\n"
        "                      d_model=8, num_heads=2, num_kv_heads=1,\n"
        "                      d_ff=16, vocab_size=32, head_dim=4)\n")
    cfg = program.arch_config({"family": "tiny"}, tmp_path)
    assert (cfg.name, cfg.d_model, cfg.head_dim) == ("tiny", 8, 4)
    with pytest.raises(ValueError, match="tiny"):
        program.arch_config({"family": "tiny"})
