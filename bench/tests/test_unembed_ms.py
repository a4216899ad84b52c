"""``unembed_ms`` and the scope paths it reads (``bench/scopes.py``), on a
hand-built trace with a known answer."""
from types import SimpleNamespace

import pytest

from bench import scopes, trace
from bench.metrics import unembed_ms

DECODE = "jit(paged_decode_step_device)"


def _ev(name, start, dur, path=None):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                           path=path)


def _paths(planes):
    """What ``scopes.paths`` reads from the trace file for device 0: the
    path of each op name that has one."""
    return {ev.name: ev.path for pl in planes if pl.name == "/device:TPU:0"
            for ln in pl.lines for ev in ln.events if ev.path}


def _planes():
    """Three decode executions (100-400, 500-800, 840-890), a prefill
    (900-1000) and a window of 0-850 that the third one crosses."""
    dev = SimpleNamespace(name="/device:TPU:0", lines=[
        SimpleNamespace(name="XLA Modules", events=[
            _ev("jit_paged_decode_step_device(1)", 100, 300),
            _ev("jit_paged_decode_step_device(1)", 500, 300),
            _ev("jit_paged_decode_step_device(1)", 840, 50),
            _ev("jit_prefill_kv_chunk(2)", 900, 100)]),
        SimpleNamespace(name="XLA Ops", events=[
            _ev("while.1", 100, 150, f"{DECODE}/decode_layers/while"),
            # the head's cast and matmul, overlapping by 10 ns
            _ev("convert.2", 250, 60, f"{DECODE}/unembed/convert:"),
            _ev("fusion.3", 300, 50, f"{DECODE}/unembed/dot_general:"),
            _ev("argmax.4", 350, 50, f"{DECODE}/sampling/argmax"),
            _ev("convert.2", 600, 40, f"{DECODE}/unembed/convert:"),
            # a scope whose name only starts with the metric's
            _ev("fusion.5", 640, 20, f"{DECODE}/unembedding/add"),
            # ends past the window's end
            _ev("fusion.3", 845, 35, f"{DECODE}/unembed/dot_general:"),
            # the prefill's own unembed is not the decode step's
            _ev("fusion.6", 900, 50,
                "jit(prefill_kv_chunk)/unembed/dot_general:"),
            _ev("copy.7", 950, 10)])])
    host = SimpleNamespace(name="/host:CPU", lines=[
        SimpleNamespace(name="python", events=[_ev("bench:window", 0, 850)])])
    return [SimpleNamespace(name="/host:metadata", lines=[]), dev, host]


def test_the_reduction_keeps_its_fields():
    red = trace.reduce_planes(_planes())
    assert red.window() == (0.0, 850.0)
    assert len(red.ops[0]) == 9 and len(red.modules[0]) == 4
    assert red.spans == [("window", 0.0, 850.0)]


def test_unembed_is_the_decode_heads_union_per_execution():
    planes = _planes()
    red = trace.reduce_planes(planes)
    lo, hi = red.window()
    # the first execution's 250-350 (a union of 100 ns) and the second's
    # 600-640, over the two executions wholly inside the window
    got = unembed_ms.unembed_ms(red, _paths(planes), lo, hi)
    assert got == pytest.approx((100 + 40) / 2 / 1e6)
    # with the window widened past the third execution, it and its head
    # count too
    got = unembed_ms.unembed_ms(red, _paths(planes), 0.0, 1000.0)
    assert got == pytest.approx((100 + 40 + 35) / 3 / 1e6)


def test_a_program_without_the_scope_reads_none():
    planes = _planes()
    red = trace.reduce_planes(planes)
    assert unembed_ms.unembed_ms(red, {}, 0.0, 1000.0) is None


def test_no_trace_reads_none(tmp_path, monkeypatch):
    import bench.run
    monkeypatch.setattr(bench.run, "TRACE_DIR", tmp_path)
    red = trace.reduce_planes(_planes())
    assert unembed_ms.read(SimpleNamespace(trace=red, lo=0.0,
                                           hi=1000.0)) is None


def test_scope_is_a_whole_component():
    assert scopes.in_scope(f"{DECODE}/unembed/dot_general:", "unembed")
    assert not scopes.in_scope(f"{DECODE}/unembedding/add:", "unembed")
    assert not scopes.in_scope("", "unembed")


def _key(num, wire):
    return _varint(num << 3 | wire)


def _varint(n):
    out = b""
    while True:
        low, n = n & 0x7F, n >> 7
        out += bytes([low | (0x80 if n else 0)])
        if not n:
            return out


def _msg(num, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _key(num, 2) + _varint(len(payload)) + payload


def _int(num, value):
    return _key(num, 0) + _varint(value)


def _xspace():
    """A TPU trace whose ops carry their scope paths only in their event
    metadata's ``tf_op`` stat, as a string or as a reference to a stat
    metadata's name; one op has none, and a double stat is skipped."""
    def stat_meta(i, name):
        return _msg(5, _int(1, i) + _msg(2, _int(1, i) + _msg(2, name)))

    def event_meta(i, name, *stats):
        return _msg(4, _int(1, i) + _msg(2, _int(1, i) + _msg(2, name)
                                        + b"".join(stats)))

    def stat(meta_id, field, value):
        body = _int(1, meta_id) + (_msg(field, value) if field == 5
                                   else _int(field, value))
        return _msg(5, body)

    double = _msg(5, _int(1, 9) + _key(2, 1) + bytes(8))
    plane = (_int(1, 1) + _msg(2, "/device:TPU:0")
             + stat_meta(7, "tf_op") + stat_meta(8, "long_name")
             + stat_meta(9, "flops")
             + stat_meta(10, f"{DECODE}/unembed/convert:")
             + event_meta(1, "jit_paged_decode_step_device(1)")
             + event_meta(2, "%fusion.109 = f32[8] fusion(%p)",
                          stat(7, 5, f"{DECODE}/unembed/dot_general:"),
                          double)
             + event_meta(3, "%convert.42 = bf16[8] convert(%p)",
                          stat(8, 5, "%convert.42 = bf16[8] convert(%p)"))
             + event_meta(4, "%copy.5 = f32[8] copy(%p)", stat(7, 7, 10)))

    def event(meta_id, start_ns, dur_ns):
        return _msg(4, _int(1, meta_id) + _int(2, start_ns * 1000)
                    + _int(3, dur_ns * 1000))

    modules = _msg(3, _int(1, 1) + _msg(2, "XLA Modules")
                   + event(1, 100, 300))
    ops = _msg(3, _int(1, 2) + _msg(2, "XLA Ops") + event(3, 100, 100)
               + event(2, 200, 60) + event(4, 280, 20))
    return _msg(1, plane + modules + ops) + _msg(
        1, _int(1, 2) + _msg(2, "/host:CPU"))


def _trace_dir(tmp_path):
    d = tmp_path / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(_xspace())
    return tmp_path


def test_scope_paths_come_from_the_trace_files_event_metadata(
        tmp_path, monkeypatch):
    got = scopes.paths(_trace_dir(tmp_path))
    assert list(got) == ["/device:TPU:0"]
    assert {n.split(" = ")[0]: p for n, p in got["/device:TPU:0"].items()} \
        == {"%fusion.109": f"{DECODE}/unembed/dot_general:",
            "%copy.5": f"{DECODE}/unembed/convert:"}
    import bench.run
    monkeypatch.setattr(bench.run, "TRACE_DIR", tmp_path)
    from jax.profiler import ProfileData
    red = trace.reduce_planes(ProfileData.from_serialized_xspace(
        _xspace()).planes)
    # one decode execution; its head ops 200-260 and 280-300
    assert unembed_ms.read(SimpleNamespace(trace=red, lo=0.0, hi=1000.0)) \
        == pytest.approx(80 / 1e6)


def test_scoped_ops_are_the_reductions_ops_with_their_paths(tmp_path):
    """The reduction's op names, as ``ProfileData`` gives them, are the
    keys of the map read from the same file."""
    from jax.profiler import ProfileData
    red = trace.reduce_planes(ProfileData.from_serialized_xspace(
        _xspace()).planes)
    got = scopes.paths(_trace_dir(tmp_path))["/device:TPU:0"]
    assert [(n.split(" = ")[0], s, e, got.get(n, ""))
            for n, s, e in red.ops[0]] == [
        ("%convert.42", 100.0, 200.0, ""),
        ("%fusion.109", 200.0, 260.0, f"{DECODE}/unembed/dot_general:"),
        ("%copy.5", 280.0, 300.0, f"{DECODE}/unembed/convert:")]
