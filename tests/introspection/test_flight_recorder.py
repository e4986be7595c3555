"""Flight-recorder units + engine integration: ring determinism, the
dump-document schema, crash hooks, and the per-step records the engine
appends (no device syncs asserted by omnilint OL2, behavior here)."""

import json

import pytest

from vllm_omni_tpu.introspection.flight_recorder import (
    SCHEMA_VERSION,
    FlightRecorder,
    build_dump,
    capture_stacks,
    dump_to_file,
)


# ------------------------------------------------------------------ ring
def test_ring_bounded_and_deterministic():
    fr = FlightRecorder(capacity=4, name="t")
    for i in range(10):
        fr.append({"i": i})
    records = fr.tail()
    assert len(records) == 4
    # seq is monotone and the surviving tail is exactly the newest 4
    assert [r["seq"] for r in records] == [7, 8, 9, 10]
    assert [r["i"] for r in records] == [6, 7, 8, 9]
    assert fr.total_steps == 10
    # dropped == seq gap at the head of the ring
    assert fr.dropped == 6
    assert fr.dropped == records[0]["seq"] - 1


def test_tail_sizes():
    fr = FlightRecorder(capacity=8)
    for i in range(5):
        fr.append({"i": i})
    assert len(fr.tail(2)) == 2
    assert fr.tail(2)[-1]["i"] == 4
    assert fr.tail(0) == []
    assert len(fr.tail(100)) == 5


def test_last_step_age():
    fr = FlightRecorder(capacity=2)
    assert fr.last_step_age_s() is None
    fr.append({})
    age = fr.last_step_age_s()
    assert age is not None and 0.0 <= age < 5.0


def test_capacity_must_be_positive():
    with pytest.raises(ValueError):
        FlightRecorder(capacity=0)


def test_snapshot_schema_json_ready():
    fr = FlightRecorder(capacity=4, name="engine-0")
    fr.append({"path": "sync", "decodes": 1})
    snap = fr.snapshot()
    for key in ("name", "capacity", "total_steps", "dropped",
                "last_step_ts", "records"):
        assert key in snap
    assert snap["records"][0]["path"] == "sync"
    assert snap["records"][0]["ts"] > 0
    json.dumps(snap)  # rides HTTP + dump files


# ------------------------------------------------------------------ dumps
def test_build_dump_schema():
    fr = FlightRecorder(capacity=4, name="a")
    fr.append({"x": 1})
    doc = build_dump("watchdog_trip", recorders=[fr],
                     extra={"watchdog": {"stalled_s": 1.0}})
    assert doc["schema_version"] == SCHEMA_VERSION
    assert doc["reason"] == "watchdog_trip"
    assert doc["pid"] > 0 and doc["ts"] > 0
    assert doc["recorders"][0]["name"] == "a"
    assert doc["watchdog"] == {"stalled_s": 1.0}
    # all-thread stacks captured by default, keyed by thread label,
    # and this very test frame is visible in its own thread's stack
    assert doc["stacks"]
    me = [frames for frames in doc["stacks"].values()
          if any("test_build_dump_schema" in line for line in frames)]
    assert me, "current frame missing from captured stacks"
    json.dumps(doc, default=str)


def test_dump_to_file_explicit_path(tmp_path):
    fr = FlightRecorder(capacity=2)
    fr.append({"i": 1})
    path = str(tmp_path / "dump.json")
    out = dump_to_file(build_dump("manual", recorders=[fr]), path)
    assert out == path
    doc = json.load(open(path))
    assert doc["reason"] == "manual"
    assert doc["recorders"][0]["records"][0]["i"] == 1


def test_dump_skipped_without_flight_dir(tmp_path, monkeypatch):
    monkeypatch.delenv("OMNI_TPU_FLIGHT_DIR", raising=False)
    assert dump_to_file(build_dump("noop")) is None


def test_dump_resolves_flight_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("OMNI_TPU_FLIGHT_DIR", str(tmp_path / "dumps"))
    fr = FlightRecorder(capacity=2)
    fr.append({})
    out = dump_to_file(build_dump("sigusr2", recorders=[fr]))
    assert out is not None and "sigusr2" in out
    assert json.load(open(out))["reason"] == "sigusr2"
    # a second dump with the SAME reason inside the per-reason
    # cooldown window is SUPPRESSED (repeated SIGUSR2s / a flapping
    # alert must not flood the incident dir) — and counted in the
    # cooldown self-view
    from vllm_omni_tpu.introspection.flight_recorder import (
        dump_cooldown,
    )

    out2 = dump_to_file(build_dump("sigusr2", recorders=[fr]))
    assert out2 is None
    snap = dump_cooldown.snapshot()
    key = f"sigusr2@{tmp_path / 'dumps'}"
    assert snap["reasons"][key]["suppressed"] == 1
    # a DIFFERENT reason is independent of the sigusr2 window
    out3 = dump_to_file(build_dump("crash", recorders=[fr]))
    assert out3 is not None and out3 != out


def test_failed_write_does_not_consume_cooldown(tmp_path, monkeypatch):
    """A write that never lands (unusable flight dir) must neither
    start the per-reason window nor register a last-dump age: the
    first retry after the disk comes back succeeds immediately."""
    blocker = tmp_path / "not-a-dir"
    blocker.write_text("")
    monkeypatch.setenv("OMNI_TPU_FLIGHT_DIR", str(blocker / "dumps"))
    fr = FlightRecorder(capacity=2)
    fr.append({})
    assert dump_to_file(build_dump("sigusr2", recorders=[fr])) is None
    from vllm_omni_tpu.introspection.flight_recorder import dump_cooldown

    assert f"sigusr2@{blocker / 'dumps'}" not in \
        dump_cooldown.snapshot()["reasons"]
    # the disk comes back: the very next attempt writes, no window owed
    monkeypatch.setenv("OMNI_TPU_FLIGHT_DIR", str(tmp_path / "dumps"))
    out = dump_to_file(build_dump("sigusr2", recorders=[fr]))
    assert out is not None and json.load(open(out))["reason"] == "sigusr2"


def test_dump_cooldown_zero_disables(tmp_path, monkeypatch):
    monkeypatch.setenv("OMNI_TPU_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv("OMNI_TPU_DUMP_COOLDOWN_S", "0")
    fr = FlightRecorder(capacity=2)
    fr.append({})
    # with the limiter off, same-reason dumps in the same second get
    # distinct filenames (the process-wide dump ordinal)
    out = dump_to_file(build_dump("sigusr2", recorders=[fr]))
    out2 = dump_to_file(build_dump("sigusr2", recorders=[fr]))
    assert out is not None and out2 is not None and out2 != out


def test_capture_stacks_covers_all_threads():
    import threading

    gate = threading.Event()
    done = threading.Event()

    def parked():
        done.set()
        gate.wait(5)

    t = threading.Thread(target=parked, name="parked-thread",
                         daemon=True)
    t.start()
    done.wait(5)
    try:
        stacks = capture_stacks()
        labels = list(stacks)
        assert any("parked-thread" in label for label in labels)
    finally:
        gate.set()


# -------------------------------------------------------- engine records
@pytest.fixture(scope="module")
def stepped_engine():
    from tests.helpers import tiny_lm_factory
    from vllm_omni_tpu.engine.llm_engine import EngineConfig, LLMEngine

    params, cfg, _ = tiny_lm_factory()
    eng = LLMEngine(params, cfg, EngineConfig(
        num_pages=32, page_size=4, max_model_len=64, max_num_seqs=4))
    eng.generate([[1, 2, 3, 4], [5, 6, 7]],
                 None)
    return eng


def test_engine_appends_step_records(stepped_engine):
    eng = stepped_engine
    records = eng.flight.tail()
    assert records, "no flight records after generate()"
    r = records[-1]
    for key in ("path", "unified", "fallback", "prefills", "decodes",
                "spec_rows", "verify_tokens", "new_tokens",
                "prefill_tokens", "prefill_ctx", "decode_ctx",
                "wide_q_tokens", "chunk_tokens_trimmed", "bucket_raised",
                "sampler_path", "waiting", "running",
                "host_ms", "device_ms", "kv_offloads", "kv_restores",
                "slot", "compiles", "requests", "seq", "ts"):
        assert key in r, f"record missing {key}"
    assert r["path"] in ("sync", "pipelined")
    # the scheduled request ids ride the record (the stuck-request
    # answer in a dump)
    assert any(rec["requests"] for rec in records)
    assert {rid for rec in records for rid in rec["requests"]} \
        >= {"req-0", "req-1"}
    json.dumps(records)


def test_spec_step_records_verify_rows():
    """Flight-recorder honesty for spec decode (schema v2): a
    verify-heavy step reports spec_rows/verify_tokens and carries the
    unified flag — /debug/flightrecorder can distinguish verify-heavy
    steps from plain decode."""
    import jax.numpy as jnp

    from tests.helpers import tiny_lm_factory
    from vllm_omni_tpu.engine.llm_engine import EngineConfig, LLMEngine

    params, cfg, _ = tiny_lm_factory()

    def draft_fn(hidden, tokens, positions):
        return jnp.tile(tokens[:, None], (1, 2))

    eng = LLMEngine(params, cfg, EngineConfig(
        num_pages=32, page_size=4, max_model_len=64, max_num_seqs=4,
        num_speculative_tokens=2), draft_fn=draft_fn)
    eng.generate([[1, 2, 3, 4]], None)
    spec_recs = [r for r in eng.flight.tail() if r["spec_rows"]]
    assert spec_recs, "no verify step recorded"
    # a full-width verify: 1 regular + 2 draft candidates (the stream's
    # last verify may be clamped by remaining max_tokens)
    assert max(r["verify_tokens"] for r in spec_recs) == 3
    for r in spec_recs:
        assert r["spec_rows"] == 1
        assert r["unified"] is True
    plain = [r for r in eng.flight.tail() if not r["spec_rows"]]
    for rec in plain:
        assert rec["verify_tokens"] == 0


def test_flight_record_carries_wide_q_tokens_and_the_snapshot_their_sum():
    """A 20-token prompt alone in its step packs as three sub-blocks of
    one owner: whatever q block the runner derived, every block is that
    sequence's, so all 20 tokens ride wide tiles.  Two prompts of 5 and
    3 tokens share a q block wider than the alignment (narrow); pure
    decode steps never reach the ragged kernel."""
    from tests.helpers import tiny_lm_factory
    from vllm_omni_tpu.engine.llm_engine import EngineConfig, LLMEngine
    from vllm_omni_tpu.ops.ragged_paged_attention import wide_q_tokens
    from vllm_omni_tpu.sampling_params import SamplingParams

    params, cfg, _ = tiny_lm_factory()
    engine = LLMEngine(params, cfg, EngineConfig(
        num_pages=32, page_size=4, max_model_len=64, max_num_seqs=4))
    sp = SamplingParams(temperature=0.0, max_tokens=2, ignore_eos=True)
    engine.add_request(list(range(1, 21)), sp)
    engine.step()
    engine.step()
    prefill, decode = engine.flight.tail(2)
    assert (prefill["prefill_tokens"], prefill["wide_q_tokens"]) == (20, 20)
    assert (decode["decodes"], decode["wide_q_tokens"]) == (1, 0)
    engine.add_request([1, 2, 3, 4, 5], sp)
    engine.add_request([6, 7, 8], sp)
    engine.step()
    pair = engine.flight.tail(1)[0]
    assert pair["prefills"] == 2
    assert pair["wide_q_tokens"] == wide_q_tokens(
        [5, 3], engine.runner.q_block)
    assert isinstance(pair["wide_q_tokens"], int)
    padding = engine.step_metrics.snapshot()["padding"]
    assert padding["wide_q_tokens_total"] == 20 + pair["wide_q_tokens"]


def test_kv_move_counts_consumed_per_record():
    """Regression: pipelined steps never run _drain_kv_moves, so the
    drain counts must be consumed by the record that reports them —
    otherwise every later record replays the last sync step's churn.
    Driven through _record_step on a stub engine (no jax, and no
    pollution of the shared fixture's ring)."""
    from types import SimpleNamespace

    from vllm_omni_tpu.core.scheduler import SchedulerOutput
    from vllm_omni_tpu.engine.llm_engine import LLMEngine

    eng = SimpleNamespace(
        runner=SimpleNamespace(compile_stats={"compiles": 0}),
        scheduler=SimpleNamespace(waiting=[], running=[]),
        flight=FlightRecorder(capacity=8),
        _inflight=None,
        _last_kv_moves=(3, 1),
    )
    record = LLMEngine._record_step
    record(eng, "pipelined", SchedulerOutput(), [], 0, 0.0, 0.0)
    record(eng, "pipelined", SchedulerOutput(), [], 0, 0.0, 0.0)
    first, second = eng.flight.tail(2)
    assert (first["kv_offloads"], first["kv_restores"]) == (3, 1)
    assert (second["kv_offloads"], second["kv_restores"]) == (0, 0)


def test_engine_registered_for_introspection(stepped_engine):
    from vllm_omni_tpu import introspection

    assert stepped_engine in introspection.iter_engines()
    recs = introspection._live_recorders()
    assert stepped_engine.flight in recs


def test_engine_progress_probe(stepped_engine):
    p = stepped_engine.introspect_progress()
    assert p["busy"] is False
    # progress counts step COMPLETIONS — at least every record-bearing
    # step, plus any zero-scheduled ticks (which are still the loop
    # turning, and must count so busy-idle states never false-trip)
    assert p["progress"] >= stepped_engine.flight.total_steps > 0
    assert p["compiles"] > 0
    assert p["compile_in_flight"] is False


def test_sampler_path_counters_ride_the_record_and_the_snapshot():
    """The step's readback carries which way the sampler's filter went
    (sample/sampler.py PATH_*): a top_k 20 batch counts as bounded, a
    top_p-alone batch over nearly flat logits (the CAP candidates do
    not reach p) as a full sort, a greedy batch as neither."""
    import jax
    import jax.numpy as jnp

    from vllm_omni_tpu.engine.llm_engine import EngineConfig, LLMEngine
    from vllm_omni_tpu.models.common import transformer as tfm
    from vllm_omni_tpu.sample.sampler import CAP
    from vllm_omni_tpu.sampling_params import SamplingParams

    cfg = tfm.TransformerConfig.tiny(vocab_size=4 * CAP)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    # the synchronous step: a record carries its own step's path (the
    # pipelined step's record carries its predecessor's, one step late)
    engine = LLMEngine(params, cfg, EngineConfig(
        num_pages=32, page_size=4, max_model_len=64, max_num_seqs=4,
        async_scheduling=False))

    def run(**sampling):
        engine.generate([[1, 2, 3], [4, 5, 6, 7]], SamplingParams(
            max_tokens=3, ignore_eos=True, **sampling))
        n = engine.flight.total_steps
        paths = [r["sampler_path"] for r in engine.flight.tail(n - run.seen)]
        run.seen = n
        return paths, dict(engine.step_metrics.snapshot()["sampler"])

    run.seen = 0
    paths, totals = run(temperature=0.0, top_k=20, top_p=0.5)
    assert set(paths) == {None}
    assert totals == {"bounded_steps_total": 0, "full_sort_steps_total": 0}
    paths, totals = run(temperature=0.7, top_k=20, top_p=0.8, seed=1)
    assert set(paths) == {"bounded"}
    assert totals == {"bounded_steps_total": len(paths),
                      "full_sort_steps_total": 0}
    bounded = len(paths)
    paths, totals = run(temperature=1.0, top_k=0, top_p=0.99, seed=2)
    assert set(paths) == {"full_sort"}
    assert totals == {"bounded_steps_total": bounded,
                      "full_sort_steps_total": len(paths)}
