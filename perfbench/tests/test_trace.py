"""The trace reduction on small hand-made traces."""
from perfbench import trace as tr


def op(s, e, name="fusion.1 f32[8]", module="jit_step"):
    return (s, e, name, module)


def test_union_merges_overlaps_and_clips():
    ivs = [op(0, 10), op(5, 20), op(30, 40), op(38, 45), op(90, 120)]
    assert tr.union(ivs, 0, 100) == [(0, 20), (30, 45), (90, 100)]
    assert tr.busy_ns(ivs, 0, 100) == 20 + 15 + 10


def test_nested_ops_count_once():
    # a while op holds its body's ops: the device is busy once, not twice
    ivs = [op(0, 100, "while.4 (s32[])"), op(10, 20), op(30, 90)]
    assert tr.busy_ns(ivs, 0, 100) == 100
    by = tr.op_time_by_name(ivs, 0, 100)
    assert by == {"jit_step/fusion f32[8]": 70}


def test_gaps_and_attribution_to_the_innermost_span():
    ops = [op(0, 10), op(50, 60), op(100, 110)]
    spans = [(5, 70, "bench.step"), (20, 40, "bench.sample"),
             (80, 105, "bench.step")]
    assert tr.gaps(ops, 0, 120) == [(10, 50), (60, 100), (110, 120)]
    idle = tr.idle_by_span(ops, spans, 0, 120)
    # 10..20 step, 20..40 sample, 40..50 step, 60..70 step, 70..80 none,
    # 80..100 step, 110..120 none
    assert idle == {"bench.step": 10 + 10 + 10 + 20, "bench.sample": 20,
                    "outside_spans": 10 + 10}
    assert sum(idle.values()) == 120 - tr.busy_ns(ops, 0, 120)


def test_names_are_stable_across_compiles():
    a = "%fusion.78 = f32[8,151936]{1,0:T(8,128)S(1)} fusion(bf16[151936])"
    b = "%fusion.91 = f32[8,151936]{1,0:T(8,128)} fusion(bf16[151936])"
    na = tr.op_name(a) + " " + tr.op_shape(a)
    nb = tr.op_name(b) + " " + tr.op_shape(b)
    assert tr.stable_op_name(na) == tr.stable_op_name(nb) == \
        "fusion f32[8,151936]"
    assert tr.module_name("jit_fused_decode_step(1176580960)") == \
        "jit_fused_decode_step"


def test_ops_take_the_module_that_covers_them():
    mods = [(0, 50, "jit_prefill_step", ""), (60, 90, "jit_decode", "")]
    ops = [(1, 5, "a"), (55, 58, "b"), (61, 70, "c")]
    got = tr._with_modules(ops, mods)
    assert [o[3] for o in got] == ["jit_prefill_step", "", "jit_decode"]


def test_time_of_kernel_by_stable_name():
    ops = [op(0, 10, "decode_attention_bkgd.7 bf16[8]"),
           op(20, 25, "decode_attention_bkgd.9 bf16[8]"),
           op(30, 40, "flash_attention_bhsd.2 bf16[1]")]
    assert tr.time_of(ops, "decode_attention_bkgd", 0, 100) == (15, 2)
    assert tr.time_of(ops, "flash_attention_bhsd", 0, 100) == (10, 1)
    assert tr.time_of(ops, "flash_attention_bhsd", 0, 35) == (0, 0)
