"""The readers of the program's own spans and counters, on hand-made
spans, device ops and window counters; and the spans' load from a real
trace written on the CPU."""
import pytest

from perfbench.metrics import (
    _program, decode_dispatch_ms, decode_wait_ms, host_draw_ms,
    pulled_bytes_per_token, sample_pull_ms, sampling_idle_share,
    tick_self_ms,
)

SPAN_READERS = (decode_dispatch_ms, decode_wait_ms, sample_pull_ms,
                host_draw_ms, tick_self_ms, sampling_idle_share)


def sp(s, e, name, **meta):
    return (s, e, name, meta)


def ctx_with(monkeypatch, spans, ops=(), lo=0, hi=1000, stats=None):
    monkeypatch.setattr(_program, "spans", lambda ctx: tuple(sorted(
        spans, key=lambda x: (x[0], -x[1]))))
    return {"trace_lo": lo, "trace_hi": hi, "ops": list(ops),
            "win": {"stats": stats}}


def op(s, e):
    return (s, e, "fusion.1 f32[8]", "jit_fused_decode_step")


# two ticks in the window, one cut by its start and one by its end
SPANS = [
    sp(0, 400, "serve.step"),
    sp(50, 60, "serve.admit", rid=1, slot=0),
    sp(52, 55, "serve.row_pull", rid=1, slot=0),
    sp(55, 58, "serve.host_draw", rid=1, slot=0),
    sp(100, 300, "serve.tick"),
    sp(100, 130, "serve.decode_dispatch"),
    sp(130, 170, "serve.decode_wait"),
    sp(180, 200, "serve.row_pull", rid=2, slot=1),
    sp(200, 250, "serve.host_draw", rid=2, slot=1),
    sp(400, 900, "serve.step"),
    sp(500, 700, "serve.tick"),
    sp(500, 510, "serve.decode_dispatch"),
    sp(510, 600, "serve.decode_wait"),
    sp(620, 640, "serve.row_pull", rid=3, slot=0),
    sp(640, 650, "serve.host_draw", rid=3, slot=0),
    sp(-50, 20, "serve.tick"),                  # starts before the trace
    sp(-50, -10, "serve.decode_wait"),
    sp(950, 1100, "serve.tick"),                # ends after it
    sp(950, 1090, "serve.decode_wait"),
]


def test_per_tick_parts_count_only_ticks_inside_the_window(monkeypatch):
    ctx = ctx_with(monkeypatch, SPANS)
    assert decode_dispatch_ms.read(ctx) == pytest.approx((30 + 10) / 2 / 1e6)
    assert decode_wait_ms.read(ctx) == pytest.approx((40 + 90) / 2 / 1e6)
    # the admission's row pull and draw lie in no tick
    assert sample_pull_ms.read(ctx) == pytest.approx((20 + 20) / 2 / 1e6)
    assert host_draw_ms.read(ctx) == pytest.approx((50 + 10) / 2 / 1e6)
    # tick 1: 200 less 140 covered; tick 2: 200 less 130 covered
    assert tick_self_ms.read(ctx) == pytest.approx((60 + 70) / 2 / 1e6)
    parts = sum(m.read(ctx) for m in SPAN_READERS[:5])
    assert parts == pytest.approx(200 / 1e6)    # the mean tick


def test_self_time_takes_the_union_of_overlapping_children(monkeypatch):
    ctx = ctx_with(monkeypatch, [
        sp(0, 100, "serve.tick"),
        sp(10, 30, "serve.decode_dispatch"),
        sp(20, 40, "serve.decode_wait"),        # overlaps the dispatch
        sp(50, 60, "serve.row_pull", rid=1, slot=0),
        sp(52, 58, "serve.host_draw", rid=1, slot=0),   # inside the pull
        sp(90, 120, "serve.decode_wait"),       # runs past the tick
    ])
    assert tick_self_ms.read(ctx) == pytest.approx((100 - 30 - 10) / 1e6)


def test_sampling_idle_is_cut_at_span_edges(monkeypatch):
    ops = [op(0, 10), op(50, 60), op(62, 64)]
    ctx = ctx_with(monkeypatch, [
        sp(0, 200, "serve.tick"),
        sp(5, 55, "serve.row_pull", rid=1, slot=0),     # idle 10..50
        sp(58, 70, "serve.host_draw", rid=1, slot=0),   # idle 60..62, 64..70
        sp(80, 90, "serve.decode_wait"),        # idle, but not sampling
        sp(95, 120, "serve.row_pull", rid=2, slot=1),   # cut at hi = 100
    ], ops=ops, hi=100)
    want = 40 + 2 + 6 + 5
    assert sampling_idle_share.read(ctx) == pytest.approx(100.0 * want / 100)
    # never above the device's whole idle share
    assert want <= 100 - (10 + 10 + 2)


def test_pulled_bytes_per_token_reads_the_window_counters(monkeypatch):
    ctx = ctx_with(monkeypatch, [], stats={"emitted_tokens": 4,
                                           "pulled_bytes": 1000})
    assert pulled_bytes_per_token.read(ctx) == 250.0


@pytest.mark.parametrize("stats", [None, {"slot_util": 0.9},
                                   {"emitted_tokens": 0, "pulled_bytes": 8}])
def test_nothing_to_read_gives_none(monkeypatch, stats):
    """A program without the spans or counters (an older engine): every
    new reader returns None, and none raises."""
    ctx = ctx_with(monkeypatch, [], ops=[op(0, 10)], stats=stats)
    for m in SPAN_READERS + (pulled_bytes_per_token,):
        assert m.read(ctx) is None, m.__name__


def test_no_trace_file_gives_no_spans(monkeypatch, tmp_path):
    from perfbench import harness

    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    ctx = {"trace_lo": 0, "trace_hi": 10, "ops": [], "win": {"stats": {}}}
    assert _program.spans(ctx) == ()
    assert tick_self_ms.read(ctx) is None


def test_spans_load_from_a_written_trace(monkeypatch, tmp_path):
    import jax

    from perfbench import harness

    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("serve.tick"):
        with jax.profiler.TraceAnnotation("serve.row_pull", rid=7, slot=3):
            jax.numpy.ones(4).sum().block_until_ready()
    with jax.profiler.TraceAnnotation("bench.tick"):
        pass
    jax.profiler.stop_trace()
    got = _program.spans({})
    assert [s[2] for s in got] == ["serve.tick", "serve.row_pull"]
    tick, pull = got
    assert tick[0] <= pull[0] and pull[1] <= tick[1]
    assert pull[3] == {"rid": 7, "slot": 3}
    ctx = {"trace_lo": tick[0], "trace_hi": tick[1], "ops": [],
           "win": {"stats": None}}
    assert sample_pull_ms.read(ctx) == pytest.approx((pull[1] - pull[0]) / 1e6)
