"""Fixtures for the benchmark's CPU tests: the checkout's root on the path,
and cells cut to a tiny size for runs on the CPU."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {"name": "tiny", "num_layers": 2, "d_model": 64, "num_heads": 2,
        "num_kv_heads": 2, "head_dim": 32, "d_ff": 128, "vocab_size": 256,
        "max_seq_len": 32, "norm": "layernorm", "norm_eps": 1e-5,
        "act": "gelu", "param_dtype": "bfloat16", "compute_dtype": "bfloat16"}

# finetune cells run a wider tiny model, whose per-leaf norms are steady
# enough for the cells' limits, on short sequences and few steps a call
TINY_ENCODER = dict(TINY, d_model=128, head_dim=64, d_ff=256)
TINY_FINETUNE = {"seq": 32, "batch": 8, "steps_per_call": 6,
                 # set as the cells' limits are, from this size's readings
                 # on the CPU: sound runs read at most 0.0033 (loss, grad),
                 # 0.0031 (change) and 0.0123 (gradient elements), the
                 # float8 control at least 0.0039, 0.0206, 0.0062 and 0.120
                 "limits": {"loss1_gap": 0.006, "grad_gap": 0.01,
                            "grad_err": 0.04, "change_gap": 0.0045,
                            "feed_bad": 0.0}}


@pytest.fixture
def tiny_cell():
    """``tiny_cell(name, **traffic)``: a cell of BENCHMARK.json with the tiny
    configuration and the given traffic values replaced."""
    from bench import harness

    bm = harness.benchmark()

    def make(name, **traffic):
        cell = harness.resolve(bm, name)
        as_run = dict(TINY)
        if cell["traffic_file"]["kind"] == "finetune":
            as_run = dict(TINY_ENCODER)
            traffic = {**TINY_FINETUNE, **traffic}
        cell["config_file"] = dict(cell["config_file"], as_run=as_run)
        cell["traffic_file"] = dict(cell["traffic_file"], **traffic)
        return cell

    return make


def run_tiny(cell, seed: int = 2 ** 33 + 7, seconds: float = 0.3,
             trace: bool = False, modes=()):
    """One run of ``cell`` on the CPU through the harness, past its look
    for a chip."""
    import time

    import jax

    from bench import harness

    return harness.run(cell, seed, seconds, trace, t_start=time.perf_counter(),
                       devices=jax.devices(), modes=modes)


RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]
