"""The served cells of the Mamba2 + attention hybrid and of the long
prompts, end to end on the CPU at tiny sizes through ``run.run_cell``
(as ``test_drivers.py`` runs the chat cell); then the same runs with the
timed path broken underneath, which must come out not correct.

The hybrid cell runs ``granite-4.0-h-micro-smoke`` (one Mamba2 and one
attention layer at GQA 4:1, width 256) in float32, and its reference
draws float32 weights to match. Its logits, divided by 8 over a head 256
wide, spread about 0.04, so the cell's limit, set for bfloat16 at full
width, would let a wrong token pass here: the tiny cell is held to
``SMOKE_LOGIT_ERROR``, float32 against float32. The long-prompt cell runs
``stablelm-1.6b-smoke`` at the chat cell's limit, as the chat cell does.
"""

import copy
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import control, harness, run
from chipbench.tests import test_drivers as td

RAG = "granite-4.0-h-micro-2t.rag"
LONGPROMPT = "stablelm-1.6b-2t.longprompt"
HYBRID_SMOKE = dict(
    arch="granite-4.0-h-micro-smoke", hidden_size=256,
    num_attention_heads=4, num_key_value_heads=1,
    shared_intermediate_size=1024, intermediate_size=1024,
    num_hidden_layers=2, layer_types=["mamba", "attention"],
    vocab_size=1024, mamba_n_heads=16, mamba_d_head=32, mamba_d_state=16,
    mamba_chunk_size=16, dtype="float32")
SMOKE = {RAG: HYBRID_SMOKE, LONGPROMPT: td.SMOKE_MODEL}
#: float32 program against the float32 reference: a served token is the
#: reference's best, and its logit the reference's within rounding
#: (errors of 1e-6 and less)
SMOKE_LOGIT_ERROR = 1e-4


def small_cell(name):
    cell = copy.deepcopy(harness.load_cell(name))
    cell.config.update(SMOKE[name])
    if name == RAG:
        cell.config["limits"] = {"logit_error": SMOKE_LOGIT_ERROR}
    # prompts over two SSD chunks and a ragged tail; more requests than
    # slots, so slots are freed and taken again
    cell.traffic.update(prompt_tokens=[8, 24, 40], output_tokens=4,
                        check_requests=6, rate_hz=8.0)
    return cell


def run_small(name, seconds=1.5):
    doc, _ = run.run_cell(small_cell(name), td.SEED, seconds, False,
                          jax.devices(), td.PEAKS, time.perf_counter())
    return doc


@pytest.mark.parametrize("name", [RAG, LONGPROMPT])
def test_new_cell_end_to_end(name):
    doc = run_small(name)
    assert doc["correct"] is True, doc["checks"]
    assert doc["failed"] == 0 and doc["attempted"] > 0
    cell = harness.load_cell(name)
    assert set(doc["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in doc["metrics"].values())
    assert list(doc)[-1] == "checks"
    assert doc["device"]["platform"] == "cpu"


def _half_batch(monkeypatch):
    """The decode step leaves the second half of the tenants out: they
    get the first tenant's logits, and their caches stay as they were
    (each cache leaf masked on its own tenant axis)."""
    from repro.core.tenancy import map_tenant_axis
    from repro.serving import engine

    orig = engine.engine_programs

    def programs(model, cache_len):
        p = orig(model, cache_len)

        def decode_all(params, tokens, caches, lengths):
            logits, new = p.decode_all(params, tokens, caches, lengths)
            R = logits.shape[0]

            def keep(ax, n, o):
                mask = jnp.arange(R) < (R + 1) // 2
                mask = mask.reshape((R,) + (1,) * (n.ndim - ax - 1))
                return jnp.where(mask, n, o)

            return (keep(0, logits, jnp.broadcast_to(logits[:1], logits.shape)),
                    map_tenant_axis(keep, new, caches))

        return engine.EnginePrograms(decode_all, p.decode_one, p.prefill,
                                     p.prefill_cont)

    monkeypatch.setattr(engine, "engine_programs", programs)


@pytest.mark.parametrize("fault", [td._altered_token, td._stale_decode_state,
                                   _half_batch])
@pytest.mark.parametrize("name", [RAG, LONGPROMPT])
def test_a_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    doc = run_small(name, seconds=1.0)
    assert doc["correct"] is False
    (check,) = doc["checks"].values()
    assert check["value"] > check["limit"]


def test_hybrid_control_in_fp8_fails_the_limit():
    """The hybrid reference in fp8 in the program's place, on greedy
    continuations of the float32 reference itself, reads an error above
    the tiny cell's limit; the float32 reference's own tokens and logits
    read 0."""
    from chipbench.reference import hybrid

    cell = small_cell(RAG)
    rng = np.random.default_rng(3)
    seqs = [(i % 2, rng.integers(1, 1024, 40).tolist(), [1] * 8)
            for i in range(8)]
    for _ in range(8):
        lg = hybrid._logits(cell.config, 11, seqs, fp8=False)
        seqs = [(t, p, [int(x) for x in lgi.argmax(-1)]) for (t, p, _), lgi
                in zip(seqs, lg)]
    values = [lgi.max(-1) for lgi in lg]
    limit = cell.config["limits"]["logit_error"]
    assert hybrid.widest_error(cell.config, 11, seqs, values) == 0.0
    reading = hybrid.control_errors(cell.config, 11, seqs).max()
    assert reading > limit
    sound = harness.RunResult(
        attempted=8, failed=0, metrics={},
        checks=[harness.Check("served_token_logit_error", 0.0, limit)],
        device={})
    assert control.control_result(sound, reading).correct is False
