"""The check decides ``correct``: whole runs of each cell at its tiny
rehearsal sizes on the CPU, past the look for a chip, with the timed path
sound, with the control, and with a fault planted underneath."""
import time
from concurrent.futures import Future

import jax.numpy as jnp
import pytest

from bench import harness

DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
SECONDS = 1.5
CELLS = ["yi-9b-16l.chat-open", "rwkv6-1.6b.chat-open"]


def run(name, seed, **kw):
    cell = harness.load_cell(name, rehearse=True)
    return cell, harness.run(cell, seed, SECONDS, False,
                             t_start=time.perf_counter(), device=DEVICE,
                             rehearse=True, **kw)


def family_module(name):
    from repro.models import rwkv6, transformer
    return rwkv6 if name.startswith("rwkv6") else transformer


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    _, res = run(name, 2147483903)
    assert res.line["correct"], res.line["checks"]
    assert res.line["attempted"] > 0 and res.line["failed"] == 0
    assert list(res.line["checks"])[-1] == "max_logit_gap"


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("seed", [101, 102, 103])
def test_control_fails_the_limit(name, seed):
    """The fp8 reference in the program's place reads above the limit
    that sound runs stay under, and the verdict calls it not correct."""
    cell, res = run(name, seed, control=True)
    limit = cell.spec["check"]["max_logit_gap"]
    assert res.line["correct"], res.line["checks"]
    assert res.readings["logit_gap"] <= limit
    assert res.readings["control_logit_gap"] > limit
    assert res.readings["control_correct"] is False


def test_sample_takes_batched_requests_first():
    from bench import traffic
    cell = harness.load_cell(CELLS[0])
    reqs = [traffic.Request(i, done=1.0, batched=i in (3, 40, 77))
            for i in range(100)]
    for seed in (1, 2, 2147483905):
        picked = harness.sample(cell, reqs, seed)
        assert len(picked) == cell.spec["check_requests"]
        assert sum(r.batched for r in picked) == 3
        assert len({r.idx for r in picked}) == len(picked)
    reqs = [traffic.Request(i, done=1.0, batched=True) for i in range(100)]
    picked = harness.sample(cell, reqs, 5)
    assert sum(r.batched for r in picked) == cell.spec["check_requests"]


def test_verdict_holds_each_number_to_its_limit():
    ok = {"failed_requests": {"value": 0, "limit": 0},
          "malformed_outputs": {"value": 0, "limit": 0},
          "checked_requests": {"value": 16, "limit": 16},
          "max_logit_gap": {"value": 0.5, "limit": 0.6}}
    assert harness.verdict(ok)
    for key, value in (("failed_requests", 1), ("malformed_outputs", 1),
                       ("checked_requests", 15), ("max_logit_gap", 0.7),
                       ("max_logit_gap", None)):
        bad = dict(ok, **{key: {"value": value, "limit": ok[key]["limit"]}})
        assert not harness.verdict(bad), key


def _altered_token(mod, monkeypatch):
    orig = mod.decode_step

    def decode_step(*a, **k):
        logits, cache = orig(*a, **k)
        return jnp.roll(logits, 1, axis=-1), cache
    monkeypatch.setattr(mod, "decode_step", decode_step)


def _state_unchanged(mod, monkeypatch):
    orig = mod.decode_step

    def decode_step(params, tokens, pos, cache, *a, **k):
        logits, _ = orig(params, tokens, pos, cache, *a, **k)
        return logits, cache
    monkeypatch.setattr(mod, "decode_step", decode_step)


def _answer_to_another_request(mod, monkeypatch):
    from repro.runtime.runtime import Runtime
    orig = Runtime.call_dag
    last = {}

    def call_dag(self, name, table, **k):
        prev = last.get(name, table)
        last[name] = table
        return orig(self, name, prev, **k)
    monkeypatch.setattr(Runtime, "call_dag", call_dag)


def _half_left_out(mod, monkeypatch):
    from repro.runtime.runtime import Runtime
    orig = Runtime.call_dag
    n = [0]

    def call_dag(self, name, table, **k):
        n[0] += 1
        if n[0] > 2 and n[0] % 2:
            return Future()                 # never answered
        return orig(self, name, table, **k)
    monkeypatch.setattr(Runtime, "call_dag", call_dag)


FAULTS = {"altered_token": _altered_token,
          "state_unchanged": _state_unchanged,
          "answer_to_another_request": _answer_to_another_request,
          "half_left_out": _half_left_out}


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(name, fault, monkeypatch):
    from bench import traffic
    monkeypatch.setattr(traffic, "WAIT_AFTER_S", 1.0)
    FAULTS[fault](family_module(name), monkeypatch)
    _, res = run(name, 2147483904)
    assert not res.line["correct"], res.line["checks"]
