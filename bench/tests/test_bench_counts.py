"""The byte and FLOP counts the rooflines and MFU shares divide by."""
import json
import os

import jax
import pytest

from bench import counts
from bench.reference.roberta import Sizes, init_params

from .conftest import ROOT, TINY


def _conf(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,n", [("roberta-base", 123_969_792),
                                    ("roberta-large", 354_085_888)])
def test_body_parameters(name, n):
    conf = _conf(name)
    assert counts.body_params(conf["as_run"]) == n == conf["body_parameters"]


def test_body_count_matches_the_seeded_weights():
    shapes = jax.eval_shape(lambda k: init_params(k, sz=Sizes.of(TINY, 3)),
                            jax.ShapeDtypeStruct((2,), "uint32"))
    n = sum(x.size for x in jax.tree.leaves(shapes["body"]))
    assert n == counts.body_params(TINY)


@pytest.mark.parametrize("name,batch,seq,gflop_per_token", [
    ("roberta-base", 32, 128, 0.5238), ("roberta-large", 16, 256, 1.8875)])
def test_train_flops_per_token(name, batch, seq, gflop_per_token):
    cfg = _conf(name)["as_run"]
    per_token = counts.train_flops_per_step(cfg, batch, seq, 3) / (batch * seq)
    assert per_token / 1e9 == pytest.approx(gflop_per_token, rel=1e-3)
    # the body's matmuls dominate: 6 FLOPs per matmul weight per token
    assert per_token > 6 * counts.body_matmul_params(cfg)


def test_fuse_bytes_and_required_time():
    n = 123_969_792
    assert counts.fuse_bytes(8, n) == 10 * n * 2
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    # bytes bound the fuse: 2.48 GB at 819 GB/s
    assert counts.fuse_required_s(8, n, peaks) == pytest.approx(
        10 * n * 2 / 819e9)
