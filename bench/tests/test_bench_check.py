"""The check that decides ``correct``, driven end to end at a size the
CPU holds: sound runs pass, the lower-precision control and each fault
the cell can have come out not correct.

Each run skips only the look for a chip (``tiny.DEVICE``); set-up, the
window, the reference and the comparison are the chip's.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run as bench_run
from bench.lib import names
from bench.tests import tiny

SEED = 2 ** 31 + 77
SECONDS = 1.0


def _run(workload, hooks=None):
    spec = tiny.spec(workload)
    return bench_run.run_cell(spec, SEED, SECONDS, False, 0.0, tiny.DEVICE,
                              hooks=hooks)


def _alter_third_token(engine):
    sample = engine._sample
    vocab = engine.model.cfg.vocab

    def altered(req, logits, step):
        tok = sample(req, logits, step)
        return (tok + 1) % vocab if step == 2 else tok
    engine._sample = altered


def _decode_keeps_state(engine):
    decode = engine._decode_masked

    def unchanged(params, cache, *args):
        before = jax.tree.map(jnp.copy, cache)
        logits, _ = decode(params, cache, *args)
        return logits, before
    engine._decode_masked = unchanged


def _dit_alters_answer(engine):
    engine.fault_hook = lambda phase, lat: np.asarray(lat) * 1.5


def _dit_keeps_state(engine):
    engine._sampler = lambda *key: (lambda params, noise, labels: noise)


@pytest.mark.parametrize("workload", ["ds67b-chat", "ds67b-decode",
                                      "dit-xl2-batch8"])
def test_sound_run_is_correct(workload):
    res = _run(workload)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    e2e = {m["name"] for m in names.cell_spec(workload)["end_to_end"]}
    assert set(res["metrics"]) == e2e
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("workload,fault", [
    ("ds67b-chat", _alter_third_token),
    ("ds67b-chat", _decode_keeps_state),
    ("ds67b-decode", _alter_third_token),
    ("ds67b-decode", _decode_keeps_state),
    ("dit-xl2-batch8", _dit_alters_answer),
    ("dit-xl2-batch8", _dit_keeps_state),
])
def test_fault_in_timed_path_is_not_correct(workload, fault):
    res = _run(workload, hooks=fault)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("workload", ["ds67b-chat", "dit-xl2-batch8"])
def test_lower_precision_control_is_not_correct(workload):
    """The reference in int4 (weights, activations, KV) in the
    program's place reads above the cell's limit."""
    spec = tiny.spec(workload)
    if spec["config"]["family"] == "llama":
        # widths at which int4 error is as large, relative to the
        # logits, as at the cell's size (at d_model 64 it is not)
        spec["config"].update(hidden_size=256, intermediate_size=512,
                              vocab_size=2048)
    out = names.driver(spec["config"]["engine"]).run(
        spec, SEED, SECONDS, None, 0.0, tiny.DEVICE, bench_run.log,
        control_bits=4)
    name = spec["workload"]["check"]["name"]
    limit = spec["workload"]["check"]["limit"]
    assert out["checks"][name]["value"] <= limit
    assert out["checks"]["control"]["value"] > limit
