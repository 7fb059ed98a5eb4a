"""The trace reduction: busy time as a union, idle gaps labelled by the
harness annotation over them, device time per executable."""
import pytest

from benchmarks.chip import trace_reduce as tr

MS = 1e6                                   # nanoseconds


def ev(name, start_ms, dur_ms, **stats):
    return tr.Ev(name, start_ms * MS, dur_ms * MS,
                 tuple((k, str(v)) for k, v in stats.items()))


def _synthetic():
    ops = {"/device:TPU:0": [
        ev("fusion.1", 0, 4), ev("fusion.2", 2, 4),      # overlap: 0-6
        ev("copy.3", 10, 5),                              # 10-15
        ev("fusion.1", 40, 10)]}                          # 40-50
    mods = {"/device:TPU:0": [
        ev("jit__lambda", 0, 6, hlo_module="jit__lambda", program_id=7),
        ev("jit__lambda", 10, 5, hlo_module="jit__lambda", program_id=9),
        ev("jit__lambda", 40, 10, hlo_module="jit__lambda",
           program_id=7)]}
    notes = [ev("harness:step", 0, 16), ev("harness:wait_arrival", 16, 22),
             ev("harness:step", 38, 14), ev("harness:drain", 52, 8)]
    return ops, mods, notes


def test_busy_is_the_union_of_op_intervals():
    s = tr.reduce_events(*_synthetic())
    assert s.window_s == pytest.approx(60 * 1e-3)
    assert s.busy_s == pytest.approx((6 + 5 + 10) * 1e-3)
    assert s.idle_share == pytest.approx(1 - 21 / 60)
    assert s.n_devices == 1


def test_device_time_per_executable():
    s = tr.reduce_events(*_synthetic())
    assert s.exe_time_s == pytest.approx({"jit__lambda#7": 16e-3,
                                          "jit__lambda#9": 5e-3})
    assert s.exe_launches == {"jit__lambda#7": 2, "jit__lambda#9": 1}


def test_top_ops_and_labelled_idle_gaps():
    s = tr.reduce_events(*_synthetic())
    assert s.top_ops[0] == ("fusion.1", pytest.approx(14e-3))
    # gaps: 6-10 (step), 15-40 (mostly wait_arrival), 50-60 (drain)
    assert s.idle_gaps[0] == ("harness:wait_arrival", pytest.approx(25e-3))
    assert s.idle_gaps[1] == ("harness:drain", pytest.approx(10e-3))
    assert s.idle_gaps[2] == ("harness:step", pytest.approx(4e-3))


def test_busy_is_averaged_over_devices():
    ops, mods, notes = _synthetic()
    ops["/device:TPU:1"] = [ev("fusion.9", 0, 60)]
    s = tr.reduce_events(ops, mods, notes)
    assert s.busy_s == pytest.approx((21 + 60) / 2 * 1e-3)


def test_exe_key_without_program_id():
    assert tr.exe_key(ev("jit_f", 0, 1)) == "jit_f"


def test_op_names_drop_the_instruction_text():
    assert tr.op_name("%fusion.10 = bf16[8,2]{1,0} fusion(%a), "
                      "kind=kLoop") == "fusion.10"
    assert tr.op_name("copy-start.3") == "copy-start.3"


def test_a_recorded_v5e_trace():
    """A trace recorded on a TPU v5e (``testdata/record_trace.py``): two
    requests through a toy engine inside the harness's annotations."""
    import dataclasses
    import json

    from benchmarks.chip.spec import HERE

    s = tr.reduce_file(str(HERE / "testdata" / "tiny_v5e.xplane.pb.gz"))
    assert s.n_devices == 1
    assert 0 < s.busy_s < s.window_s
    steps = [k for k in s.exe_launches if k.startswith("jit__lambda(")]
    assert len(steps) == 2                  # the prefill and decode shapes
    assert all(" = " not in name for name, _ in s.top_ops)
    assert {label for label, _ in s.idle_gaps} <= {
        "harness:submit", "harness:step", "harness:wait_arrival",
        "harness:drain"}
    assert s.idle_gaps[0][0] == "harness:wait_arrival"
    want = json.loads((HERE / "testdata" /
                       "tiny_v5e.summary.json").read_text())
    got = json.loads(json.dumps(dataclasses.asdict(s)))
    assert got["exe_launches"] == want["exe_launches"]
    assert got["window_s"] == pytest.approx(want["window_s"])
    assert got["busy_s"] == pytest.approx(want["busy_s"])
    assert [n for n, _ in got["top_ops"]] == [n for n, _ in want["top_ops"]]
    assert [t for _, t in got["top_ops"]] == \
        pytest.approx([t for _, t in want["top_ops"]])
