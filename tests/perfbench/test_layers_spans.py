"""The readers of what the program records about itself
(``perfbench/layers_spans.py``): on a recording that holds ``dkt:`` spans,
their stats and scoped operations (``recorded_trace_spans.json``), on
request times made by hand, and on the older recording, which holds none
of it, where every one of them has to return ``None``."""

import importlib
import json
import os
import types

import numpy as np
import pytest

from perfbench import common, layers_spans, run as bench, trace
from perfbench.kinds.serve import Served
from perfbench.trace import Line

from toybench import REPO

HERE = os.path.dirname(os.path.abspath(__file__))


def read(name, L):
    return importlib.import_module(f"perfbench.metrics.{name}").read(L)


NEW_METRICS = [m["name"] for m in bench.load_manifest(REPO)["per_layer"]
               if importlib.import_module(
                   f"perfbench.metrics.{m['name']}"
               ).read.__module__ == "perfbench.layers_spans"]


def load(file_name):
    with open(os.path.join(HERE, file_name)) as f:
        fix = json.load(f)
    lines = {(l["plane"], l["line"]): Line(
        [fix["names"][i] for i in l["name_index"]],
        np.array(l["start_ns"], float), np.array(l["dur_ns"], float))
        for l in fix["lines"]}
    return fix, lines


def request(index, due, phase="window"):
    return types.SimpleNamespace(index=index, due=due, phase=phase,
                                 prompt=np.zeros(8, np.int32), budget=4)


def served_by_hand(with_stamps=True):
    """Three requests over five engine steps that end at 1.0, 1.1, ...
    1.4; the window is (0.95, 1.45].  Request 2 belongs to the pre-roll."""
    s = Served([request(0, 0.90), request(1, 1.02),
                request(2, 0.10, phase="preroll")], t_span0=0.0)
    s.end = [1.0, 1.1, 1.2, 1.3, 1.4]
    s.begin = [e - 0.09 for e in s.end]

    def result(t_submit, t_admit, t_tokens):
        res = {"tokens": np.arange(len(t_tokens)), "t_submit": t_submit,
               "t_first": t_tokens[0], "t_finish": t_tokens[-1] + 0.001}
        if with_stamps:
            res.update(t_admit=t_admit, t_tokens=list(t_tokens))
        return res

    s.results = {
        # admitted in step 0 after 12 ms, first token 30 ms later, 20 ms
        # before the step's return; then one token a step
        0: (result(0.900, 0.912, [0.980, 1.070, 1.170, 1.270]), 3),
        # waits 60 ms for the step in flight; first token in step 2
        1: (result(1.040, 1.100, [1.150, 1.270, 1.372]), 4),
        2: (result(0.100, 0.101, [0.150, 1.070, 1.170]), 2),
    }
    return s


@pytest.fixture()
def times():
    return types.SimpleNamespace(served=served_by_hand(), t_open=0.95,
                                 t_close=1.45)


def test_request_times_by_hand(times):
    # nearest-rank 90th of two values is the larger
    assert read("steady_queue_wait_p90_ms", times) == pytest.approx(60.0)
    assert read("steady_admit_to_first_token_p90_ms", times) == \
        pytest.approx(68.0)
    # request 0: its step returned at 1.0, the token was there at 0.980;
    # request 1: step 2 returned at 1.2, the token was there at 1.150
    assert read("steady_first_token_handback_p90_ms", times) == \
        pytest.approx(50.0)


def test_gaps_between_tokens_as_the_program_stamped_them(times):
    # all seven gaps end inside the window, the pre-roll request's too
    # (it counts, as it does for tpot_p95_ms): the 95th is the largest
    gaps = [0.090, 0.100, 0.100, 0.120, 0.102, 0.920, 0.100]
    assert read("steady_tpot_produced_p95_ms", times) == pytest.approx(
        1e3 * common.percentile(gaps, 95))
    times.t_close = 1.25     # now the gaps that end at 1.270 and 1.372 go
    assert read("steady_tpot_produced_p95_ms", times) == pytest.approx(
        1e3 * common.percentile([0.090, 0.100, 0.120, 0.920, 0.100], 95))


def test_results_without_the_programs_stamps_give_nothing():
    old = types.SimpleNamespace(served=served_by_hand(with_stamps=False),
                                t_open=0.95, t_close=1.45)
    for name in ("steady_queue_wait_p90_ms",
                 "steady_admit_to_first_token_p90_ms",
                 "steady_first_token_handback_p90_ms",
                 "steady_tpot_produced_p95_ms"):
        assert read(name, old) is None, name


# ---- the wire reader ----------------------------------------------------

def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, value):
    """A varint field for an int, a length-delimited one for bytes/str."""
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


def map_entry(number, key, message):
    return field(number, field(1, key) + field(2, message))


OP = "%scatter.160 = bf16[32,16,512,128]{3,1,2,0} scatter(%p, %i, %u)"
OP_NAME = ("jit(step_impl_512)/while/body/closed_call/TransformerLM/Block_0/"
           "SelfAttention_0/kv_write/scatter:")


def xspace_by_hand():
    """One device plane: an operation whose metadata holds a ``tf_op``
    stat (text), a ``hlo_category`` stat (a reference) and whose one
    event holds a stat of its own."""
    stat = lambda mid, **v: (  # noqa: E731
        field(1, mid) + b"".join(field({"text": 5, "ref": 7, "int": 4}[k], x)
                                 for k, x in v.items()))
    meta = field(1, 1) + field(2, OP) + field(5, stat(1, text=OP_NAME)) \
        + field(5, stat(3, ref=4))
    event = field(1, 1) + field(2, 1000) + field(3, 5000) \
        + field(4, stat(2, int=42))
    line = field(1, 1) + field(2, "XLA Ops") + field(3, 10) + field(4, event)
    plane = field(1, 7) + field(2, "/device:TPU:0") + field(3, line) \
        + map_entry(4, 1, meta)
    for i, name in enumerate(("tf_op", "ev_stat", "hlo_category",
                              "data formatting"), start=1):
        plane += map_entry(5, i, field(1, i) + field(2, name))
    return field(1, plane)


def test_wire_reader_sees_the_op_name_that_profile_data_hides():
    from jax.profiler import ProfileData

    raw = xspace_by_hand()
    (plane,) = ProfileData.from_serialized_xspace(raw).planes
    (line,) = plane.lines
    (ev,) = line.events
    assert (plane.name, line.name, ev.name) == ("/device:TPU:0", "XLA Ops",
                                                OP)
    assert list(ev.stats) == [("ev_stat", 42)]   # the metadata's are not here
    assert layers_spans.xplane_metadata(raw) == {"/device:TPU:0": {OP: {
        "tf_op": OP_NAME, "hlo_category": "data formatting"}}}


def test_second_pass_of_a_written_trace(tmp_path):
    path = tmp_path / "plugins" / "profile" / "t" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(xspace_by_hand())
    got = layers_spans.read_second_pass(str(tmp_path))
    assert got == {"spans": [], "op_names": {OP: OP_NAME}}
    assert layers_spans.read_second_pass(str(tmp_path / "none")) == {
        "spans": [], "op_names": {}}


# ---- the recording with spans, stats and scoped operations --------------

def raster_idle_ns(dev: Line, a, b, step=100.0):
    """Idle time of the device line inside [a, b] by brute force."""
    cells = np.zeros(int(round((b - a) / step)), bool)
    for s, d in zip(dev.start, dev.start + dev.dur):
        lo, hi = max(s, a), min(d, b)
        if hi > lo:
            cells[int((lo - a) / step):int(np.ceil((hi - a) / step))] = True
    return (~cells).sum() * step


@pytest.fixture(scope="module")
def recorded():
    fix, lines = load("recorded_trace_spans.json")
    return types.SimpleNamespace(
        lines=lines, trace=trace, busy=trace.busy(lines), fix=fix,
        spans_pass={"spans": fix["spans"], "op_names": fix["op_names"]},
        steps=32)


def test_recording_holds_the_step_tree(recorded):
    names = [s["name"] for s in recorded.fix["spans"]]
    assert names.count("dkt:engine_step") == 2
    assert names.count("dkt:decode_fetch") == 6     # three pools a step
    assert names.count("dkt:prefill") == 1


def test_idle_split_of_the_engine_step(recorded):
    parts = {p: read(f"steady_idle_in_{p}_ms", recorded)
             for p in ("admit", "prefill", "dispatch", "fetch", "emit")}
    assert all(v is not None and v >= 0 for v in parts.values()), parts
    # by brute force, for the largest: the device's idle cells inside the
    # six fetch spans, over two steps
    dev = recorded.lines["/device:TPU:0", "XLA Ops"]
    want = sum(raster_idle_ns(dev, s["start"], s["start"] + s["dur"])
               for s in recorded.fix["spans"]
               if s["name"] == "dkt:decode_fetch") * 1e-6 / 2
    assert parts["fetch"] == pytest.approx(want, rel=0.02)
    assert max(parts, key=parts.get) == "fetch"
    # the parts lie inside the benchmark's span around step(), so their
    # sum cannot pass the number read from outside
    outside = read("steady_engine_step_host_ms", recorded)
    assert outside - 0.5 < sum(parts.values()) <= outside
    for p, v in parts.items():
        assert read(f"backlog_idle_in_{p}_ms", recorded) == v


def test_useful_share_of_the_prefill(recorded):
    assert read("steady_prefill_useful_token_share", recorded) == \
        pytest.approx(100 * 127 / 512)


def test_share_of_the_operations_under_a_scope(recorded):
    dev = recorded.lines["/device:TPU:0", "XLA Ops"]
    own = trace.self_seconds(dev)
    under = sum(s for n, s in own.items()
                if "/kv_write/" in recorded.fix["op_names"].get(n, ""))
    assert under > 0
    got = read("steady_kv_write_device_share", recorded)
    assert got == pytest.approx(100 * under / recorded.busy["busy_s"],
                                rel=1e-6)
    assert 0 < got < 3      # the scatters; the whole-pool copies are not
    assert read("backlog_kv_write_device_share", recorded) == got
    # a serving trace: nothing in it is under the optimizer's scope
    assert read("train_optimizer_device_share", recorded) is None
    assert layers_spans._scope_device_share(recorded, "attn_decode") > got


def test_a_scope_matches_whole_components_only():
    L = types.SimpleNamespace(
        lines={("/device:TPU:0", "XLA Ops"): Line(
            ["a", "b", "c", "d"], np.array([0., 10., 20., 30.]),
            np.array([10., 10., 10., 10.]))},
        trace=trace, busy={"busy_s": 40e-9},
        spans_pass={"spans": [], "op_names": {
            "a": "jit(run)/backward/transpose(jvp(forward_loss))/mul:",
            "b": "jit(run)/optimizer_update/add:",
            "c": "jit(run)/my_optimizer_update_2/add:",
            "d": "jit(run)/forward_loss:"}})
    assert layers_spans._scope_device_share(L, "optimizer_update") == \
        pytest.approx(25.0)
    assert layers_spans._scope_device_share(L, "forward_loss") == \
        pytest.approx(50.0)
    assert layers_spans._scope_device_share(L, "jvp") is None


def test_training_idle_is_read_from_the_trainers_spans(recorded):
    # no trainer span in a serving trace
    assert read("train_idle_in_loss_fetch_ms", recorded) is None
    lines = dict(recorded.lines)
    lines["/host:CPU", "trainer"] = Line(
        ["dkt:chunk_dispatch", "dkt:loss_fetch"],
        np.array([1.0e6, 2.0e6]), np.array([0.5e6, 3.0e6]))
    L = types.SimpleNamespace(lines=lines, trace=trace, steps=16)
    dev = lines["/device:TPU:0", "XLA Ops"]
    want = (raster_idle_ns(dev, 1.0e6, 1.5e6)
            + raster_idle_ns(dev, 2.0e6, 5.0e6)) * 1e-6 / 16
    assert read("train_idle_in_loss_fetch_ms", L) == pytest.approx(
        want, rel=0.02, abs=1e-4)


# ---- a program from before PR 26 gives every reader nothing -------------

@pytest.fixture(scope="module")
def old_program():
    _, lines = load("recorded_trace.json")
    return types.SimpleNamespace(
        lines=lines, trace=trace, busy=trace.busy(lines),
        spans_pass={"spans": [], "op_names": {}}, steps=2,
        served=served_by_hand(with_stamps=False), t_open=0.95, t_close=1.45)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_returns_none_on_the_old_recording(old_program, name):
    assert read(name, old_program) is None


def test_the_manifest_holds_the_nineteen_new_metrics():
    assert len(NEW_METRICS) == 19
