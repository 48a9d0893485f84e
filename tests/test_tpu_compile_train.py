"""The ``qwen1.5-32b.train`` cell's step compiles for a TPU v5e 2x2 host.

Nothing runs: the trainer's step at the cell's exact shapes (published
widths and attention, the file's cut of 4 layers and a quarter of the
vocabulary, its global batch, sequence length and microbatches) is lowered
for a described (not attached) ``v5e:2x2`` topology on a 2x2
``(data, model)`` mesh and compiled with the TPU compiler.  It must leave
1.5 GiB of the compiler's 15.75 GiB per chip to spare, and attention must
see only the rows of its data shard.

The topology is described inside a module-scoped fixture, never at import
(only one process may load the TPU library unless the environment allows
more).
"""
from __future__ import annotations

import dataclasses
import json
import os
import re

import jax
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.train.loop import Trainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM = 15.75 * 2**30           # what the compiler gives a v5e program
SPARE = 1.5 * 2**30


@pytest.fixture(scope="module")
def topology():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:               # no TPU compiler installed
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _cell():
    with open(os.path.join(REPO, "bench", "configs", "qwen1.5-32b.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(REPO, "bench", "mixes", "train.json")) as f:
        mix = json.load(f)
    return cfg, mix


def test_qwen1_5_32b_train_step_fits_a_v5e_2x2(topology):
    cfg, mix = _cell()
    prog = cfg["program"]
    mcfg = dataclasses.replace(get_config(cfg["program_config"]),
                               n_layers=cfg["num_hidden_layers"],
                               vocab=cfg["vocab_size"])
    assert (mcfg.q_heads, mcfg.kv_heads) == (40, 8)
    shape = (prog["mesh"]["data"], prog["mesh"]["model"])
    mesh = make_mesh(shape, ("data", "model"), topology.devices)
    b, s = mix["global_batch"], mix["seq_len"]
    trainer = Trainer(mcfg, global_batch=b, seq_len=s, mesh=mesh,
                      microbatches=prog["microbatches"])
    compiled = trainer.compiled()
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert used <= HBM - SPARE, f"{used / 2**30:.2f} GiB per chip"
    # attention's operands (B, S, H, hd) and scores (B, H, q, k) carry the
    # rows of one data shard, and the heads of one model shard
    text = compiled.as_text()
    heads = "|".join(str(h) for h in (40, 20, 8, 4))
    glob = re.findall(rf"\[{b},\d+,(?:{heads}),{mcfg.head_dim}\]"
                      rf"|\[{b},(?:{heads}),\d+,\d+\]", text)
    assert not glob, sorted(set(glob))[:5]
    local = b // shape[0]
    assert re.search(rf"\[{local},{40 // shape[1]},\d+,\d+\]", text)
