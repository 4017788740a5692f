"""The serving session's window-readable counters (``StreamSession.
counters``): hand counts on a session whose lengths are known, on the
synchronous loop, the deferred-observation (overlap) loop and two shards,
and ``last_stats`` built from the same snapshot."""
import jax
import numpy as np
import pytest

from conftest import tiny_dense
from repro.core.lora import init_adapters
from repro.models.api import get_model
from repro.serving.engine import MultiTenantEngine, Request, ServeConfig
from repro.serving.registry import AdapterRegistry
from repro.serving.scheduler import COUNTERS, Scheduler

# Two requests, prompts of 5 tokens, budgets 5 and 13, blocks of 4 tokens,
# decode chunks of at most 4 steps.  The widest span is 5 + 13 = 18
# tokens, so each of the 2 slots' tables is 5 blocks wide.
#   prefill: both prompts in one chunk (10 tokens), each emits one token;
#   decode 1: both rows, 4 steps from length 5: attended lengths 6..9 ->
#             blocks 2, 2, 2, 3 a row -> 18; A reaches its budget;
#   decode 2: B alone, 4 steps from 9: 10..13 -> 3, 3, 3, 4 -> 13;
#   decode 3: B alone, 4 steps from 13: 14..17 -> 4, 4, 4, 5 -> 17.
# Decode 2 leaves B with 4 tokens to go after it, so the overlap loop
# defers its observation; decodes 1 and 3 end a request and cannot defer.
EXPECTED = {"steps": 12, "decode_dispatches": 3, "prefill_dispatches": 1,
            "prompt_tokens": 10, "prefix_hit_tokens": 0, "preemptions": 0,
            "decode_row_steps": 2 * 4 + 4 + 4, "prefill_tokens": 10,
            "kv_grid_blocks": 2 * 5 * 12, "kv_live_blocks": 18 + 13 + 17}


@pytest.fixture(scope="module")
def engine():
    cfg = tiny_dense(dtype="float32", param_dtype="float32")
    model = get_model(cfg)
    reg = AdapterRegistry(cfg, capacity=2)
    for i in range(2):
        reg.register(f"c{i}", init_adapters(jax.random.PRNGKey(i + 1), cfg))
    return MultiTenantEngine(model, cfg, model.init(jax.random.PRNGKey(0)),
                             reg)


def _requests(cfg):
    prompt = np.arange(1, 6, dtype=np.int32) % cfg.vocab_size
    return [Request("c0", prompt, max_new_tokens=5),
            Request("c1", prompt + 7, max_new_tokens=13)]


@pytest.mark.parametrize("overlap,shards", [(False, 1), (True, 1),
                                            (False, 2)],
                         ids=["sync", "overlap", "two_shards"])
def test_counter_deltas_match_hand_counts(engine, monkeypatch, overlap,
                                          shards):
    deferred = []
    orig = Scheduler.observe_chunk_counts

    def spy(self, n):
        deferred.append(n)
        return orig(self, n)
    monkeypatch.setattr(Scheduler, "observe_chunk_counts", spy)
    sc = ServeConfig(batch_size=2, block_size=4, prefill_chunk=8,
                     scan_chunk=4, overlap=overlap, num_shards=shards)
    ses = engine.session(sc, _requests(engine.cfg))
    assert ses.counters() == dict.fromkeys(COUNTERS, 0)
    ses.step()                                   # the prefill chunk
    after_prefill = ses.counters()
    assert after_prefill["prefill_tokens"] == 10
    assert after_prefill["steps"] == 0
    while ses.has_work:
        ses.step()
    total = ses.counters()
    assert total == EXPECTED
    decode = {k: total[k] - after_prefill[k] for k in COUNTERS}
    assert decode["prefill_tokens"] == 0
    assert decode["decode_row_steps"] == EXPECTED["decode_row_steps"]
    assert deferred == ([4] if overlap else [])
    # occupancy as the counters define it: 16 of 24 slot-steps
    assert total["decode_row_steps"] / (total["steps"] * 2) == \
        pytest.approx(2 / 3)


@pytest.mark.parametrize("overlap", [False, True], ids=["sync", "overlap"])
def test_last_stats_counters_are_the_snapshot(engine, overlap):
    sc = ServeConfig(batch_size=2, block_size=4, prefill_chunk=8,
                     scan_chunk=4, overlap=overlap)
    ses = engine.session(sc, _requests(engine.cfg))
    while ses.has_work:
        ses.step()
    snap = ses.counters()
    stats = ses.finalize()
    assert stats["decode_steps"] == snap["steps"]
    assert {k: stats[k] for k in COUNTERS if k != "steps"} == \
        {k: v for k, v in snap.items() if k != "steps"}
    assert "victim_sealed_fraction_mean" not in stats
