"""CPU tests of ``bench/spans.py``, the reduction of the program's own spans
(``repro.*``) and movement scopes from a benchmark trace: on a synthetic
trace, and on the recorded chip trace the harness's reduction was built on
(no program spans there: every reading the harness gives stays what it
was)."""
from __future__ import annotations

import json
import pathlib
from types import SimpleNamespace

import pytest

from bench import check, model, run, spans as S, trace as T

BENCH = pathlib.Path(__file__).resolve().parents[1]
FIXTURES = BENCH / "tests" / "trace_fixture"
# 2 s of a chat mix before the program had spans (bench/tests/test_bench.py)
OLD = FIXTURES / "chat_2s.xplane.pb.gz"


# ---- a synthetic trace ---------------------------------------------------
def synthetic() -> S.Program:
    """Two ticks on a window of 100 ns.  Chip 0 is busy 10-30 (a decode)
    and 50-60 (a suspend whose 52-58 is under movement scopes); the host
    reads the tokens 30-34 and suspends 36-48 in the first tick, and waits
    for an arrival outside any tick 62-80."""
    ops = [("%fusion.1 = f32[4] fusion()", 10, 30),
           ("%fusion.2 = u8[8] fusion()", 50, 60)]
    mods = [("jit_decode_step_batched(1)", 10, 30),
            ("jit__suspend_body(2)", 50, 60)]
    host = [("bench.window", 0, 100), ("bench.tick", 0, 62),
            ("bench.step_end", 29, 49), ("bench.tick", 80, 100)]
    tr = T.Trace(window=(0, 100), modules=[mods], ops=[ops], host=host)
    spans = [("repro.tick", 1, 61, {"tick": 1, "queued": 0}),
             ("repro.admit", 2, 3, {"uids": "7;8"}),
             ("repro.step_begin", 4, 9, {"active": 1}),
             ("repro.dispatch", 5, 8, {"program": "decode_step_batched"}),
             ("repro.step_end", 29, 48, {"completed": 1}),
             ("repro.read", 30, 34, {"what": "tokens"}),
             ("repro.bookkeep", 34, 36, {}),
             ("repro.suspend", 36, 48, {"uids": 5, "width": 1}),
             ("repro.dispatch", 46, 48, {"program": "_suspend_body"}),
             ("repro.wave", 49, 61, {}),
             ("repro.submit", 50, 60, {"uid": 7, "bucket": 16}),
             ("repro.tick", 81, 99, {"tick": 2, "queued": 1}),
             ("repro.read", 82, 90, {"what": "tags"})]
    return S.Program(trace=tr, spans=sorted(spans, key=lambda s: (s[1],
                                                                   -s[2])),
                     modules=mods, moves=[("pack_pages", 52, 55),
                                          ("checksum", 55, 58)])


def test_host_idle_share_counts_idle_inside_ticks():
    p = synthetic()
    # idle 0-10, 30-50, 60-100; ticks cover 1-10, 30-50, 60-61, 81-99
    assert S.host_idle_share(p) == pytest.approx(9 + 20 + 1 + 18)
    assert S.host_idle_share(p) <= 100 * (1 - T.busy_s(p.trace)
                                          / p.trace.window_s)


def test_tick_host_ms_leaves_out_the_reads():
    p = synthetic()
    assert S.tick_host_ms(p) == pytest.approx((60 - 4 + 18 - 8) / 2 * 1e-6)


def test_sched_waits_run_from_admission_to_a_slot():
    p = synthetic()
    # uid 7 admitted at 2, prefilled at 50; uid 8 still queued at the close
    assert sorted(S.sched_waits_ms(p)) == pytest.approx([48e-6, 98e-6])


def test_movement_share_and_legs():
    p = synthetic()
    assert S.movement_device_share(p) == pytest.approx(100 * 6 / 30)
    assert S.movement_legs_s(p) == pytest.approx({"pack_pages": 3e-9,
                                                  "checksum": 3e-9})


def test_decode_runs_after_its_dispatch():
    assert S.dispatch_first_share(synthetic()) == 100.0
    late = synthetic()
    late.spans[3] = ("repro.dispatch", 11, 12,
                     {"program": "decode_step_batched"})
    assert S.dispatch_first_share(late) == 0.0


def test_idle_goes_to_the_innermost_span():
    idle = dict(S.top_idle(synthetic()))
    # gap 0-10 (mid 5): the decode's dispatch; 30-50 (mid 40): the suspend's
    # host work; 60-100 (mid 80): the second bench.tick opens at 80
    assert idle == {"repro.dispatch:decode_step_batched": 10e-9,
                    "repro.suspend": 20e-9, "bench.tick": 40e-9}
    # the one suspend completes a request inside the step's end: no
    # preemption
    assert S.preemptions(synthetic()) == 0
    self_s = S.self_time_s(synthetic())
    assert self_s["repro.suspend"] == pytest.approx(10e-9)
    assert self_s["repro.read:tokens"] == pytest.approx(4e-9)


# ---- the trace the harness's reduction was built on ------------------------
@pytest.fixture(scope="module")
def old():
    tr = T.load(str(OLD))
    return tr, S.load(str(OLD), tr)


def test_old_trace_has_no_program_numbers(old):
    tr, p = old
    assert not p.spans and not p.moves and len(p.modules) == 62
    for f in (S.host_idle_share, S.tick_host_ms, S.sched_wait_p75_ms,
              S.movement_device_share, S.dispatch_first_share):
        assert f(p) is None
    # with no program span the attribution is the harness's own
    assert S.top_idle(p) == T.top_idle(tr)


def test_old_trace_reads_as_before(old):
    """The harness's reduction and readers give what they gave before the
    program had spans (values on a fixed record).  The two that count bytes
    read half of what they read at float32 counts (36.872517171020654 and
    0.04038902621121944): every byte is now counted at the configuration's
    bfloat16."""
    tr, _ = old
    conf = json.loads((BENCH / "configs" /
                       "mistral-7b-v0.3-d4.json").read_text())
    rec = {"decode": [(1, 40 + i) for i in range(49)], "prefill": [19, 23],
           "suspend": [[77]], "resume": [], "parts": {}}
    build = model.builder(conf)
    r = SimpleNamespace(trace=tr, peaks=check.peaks("TPU v5 lite"), rec=rec,
                        cfg=build.model_config(conf), conf=conf, model=build)
    assert {m: run.reader(m)(r) for m in (
        "device_idle_share", "decode_roofline", "prefill_ms_per_ktok",
        "page_kernel_roofline", "resume_wave_ms")} == {
        "device_idle_share": 64.3125827191084,
        "decode_roofline": 18.436258585510327,
        "prefill_ms_per_ktok": 781.257880952381,
        "page_kernel_roofline": 0.02019451310560972,
        "resume_wave_ms": None}
    assert T.busy_s(tr) == 0.7204933410000001
    assert T.program_time(tr, "decode_step_batched") == (
        0.6536901850000001, 49)
    assert T.top_idle(tr)[:2] == [["bench.idle", 1.142405054],
                                  ["bench.step_end", 0.14073879699999883]]

