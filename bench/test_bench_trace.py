"""The trace reduction, on a small CPU trace recorded with
``bench/testdata/make_cpu_trace.py``, and on TPU-shaped event names."""

import os

import pytest

from bench import trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "testdata", "cpu_step.xplane.pb")
HLO = os.path.join(HERE, "testdata", "cpu_step.hlo.txt")


@pytest.fixture(scope="module")
def reduced():
    return tr.read(TRACE, window_ns=10 ** 9)


@pytest.fixture(scope="module")
def op_names():
    with open(HLO) as f:
        return tr.hlo_op_names(f.read())


def test_reads_one_device_with_three_step_executions(reduced):
    assert len(reduced.devices) == 1
    dev = reduced.devices[0]
    runs = [(s, e) for s, e, m in dev.modules if m == "jit_step"]
    assert len(runs) == 3
    assert 0 < tr.busy_ns(dev) <= sum(e - s for s, e in runs)


def test_scope_split_attributes_the_exchange_matmul(reduced, op_names):
    dev = reduced.devices[0]
    coll, scoped, other = tr.split_ns(dev, op_names, "exchange/")
    assert coll == 0
    assert scoped > 0 and other > 0
    total = sum(o.end - o.start for o in dev.ops)
    assert coll + scoped + other == total
    inside = {o.name for o in dev.ops
              if "exchange/" in op_names.get(o.name, "")}
    assert inside == {"dot_general.2", "wrapped_tanh"}


def test_breakdown_lists_at_most_ten_entries(reduced, op_names):
    dev = reduced.devices[0]
    top = tr.top_ops(dev, op_names)
    assert 0 < len(top) <= 10
    assert top == sorted(top, key=lambda kv: -kv[1])
    gaps = tr.idle_gaps(dev, reduced.host, min_ns=0)
    assert len(gaps) <= 10 and all(s > 0 for _, s in gaps)


def test_union_counts_overlaps_once():
    assert tr.union_ns([(0, 10), (5, 15), (20, 30)]) == 25
    assert tr.union_ns([(0, 10), (2, 3)]) == 10
    assert tr.union_ns([]) == 0


def test_tpu_event_names_parse_to_instruction_and_opcode():
    fusion = ("%convert_reduce_fusion = (f32[]{:T(128)}, bf16[2048,2048]"
              "{1,0:T(8,128)(2,1)S(1)}) fusion(bf16[2048,2048]{1,0:T(8,128)"
              "(2,1)} %x.1), kind=kOutput, calls=%fused_computation")
    assert tr._parse_op(fusion) == ("convert_reduce_fusion", "fusion")
    ar = ("%all-reduce.3 = f32[1024]{0:T(1024)} all-reduce(f32[1024]{0} "
          "%p), replica_groups={{0,1,2,3}}, to_apply=%add")
    name, code = tr._parse_op(ar)
    assert (name, code) == ("all-reduce.3", "all-reduce")
    assert tr.is_collective(tr.Op(0, 1, name, code, ""))
    assert not tr.is_collective(tr.Op(0, 1, "convert_reduce_fusion",
                                      "fusion", ""))
    a2a = "%all-to-all-start.1 = (s8[4,64]) all-to-all-start(s8[4,64] %q)"
    assert tr.is_collective(tr.Op(0, 1, *tr._parse_op(a2a), ""))


def test_hlo_op_names_reads_metadata():
    text = ('  %fusion.7 = bf16[8]{0} fusion(%a), kind=kLoop, '
            'metadata={op_name="jit(step)/exchange/bucket1/pack/x" '
            'stack_frame_id=3}\n'
            '  ROOT %add.1 = f32[] add(%b, %c)\n')
    assert tr.hlo_op_names(text) == {
        "fusion.7": "jit(step)/exchange/bucket1/pack/x", "add.1": ""}
