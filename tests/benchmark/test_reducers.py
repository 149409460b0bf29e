"""The reduction from traces, counters and shapes to metrics."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import device, flops, readers, trace as tr  # noqa: E402

RECORDED = os.path.join(ROOT, "benchmark", "testdata", "tiny.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    """Four runs of ``jit_tiny_step`` on one v5e with 20 ms host sleeps
    between them, under ``bench.window`` / ``bench.step`` / ``bench.wait``
    spans (my chip run, PR 24)."""
    return tr.load(RECORDED)


def test_union_clip_gaps_on_hand_made_intervals():
    busy = tr.union([(3.0, 4.0), (0.0, 1.0), (0.5, 2.0), (5.0, 5.0)])
    assert busy == [(0.0, 2.0), (3.0, 4.0)]
    assert tr.total(busy) == 3.0
    assert tr.clip(busy, 1.0, 3.5) == [(1.0, 2.0), (3.0, 3.5)]
    assert tr.gaps(busy, -1.0, 6.0) == [(-1.0, 0.0), (2.0, 3.0), (4.0, 6.0)]


def test_recorded_trace_has_one_device_and_the_harness_spans(recorded):
    assert [d.name for d in recorded.devices] == ["/device:TPU:0"]
    assert recorded.has_device_ops
    names = {name for name, _, _ in recorded.spans}
    assert names == {"bench.window", "bench.step", "bench.wait"}
    assert len(recorded.devices[0].modules) == 4


def test_busy_union_and_idle_share_of_the_recorded_trace(recorded):
    busy, length = tr.busy_seconds(recorded)
    # four ~11 us programs (three inside the window) in the window span
    assert 3.0e-5 < busy < 5.0e-5
    assert 0.060 < length < 0.090
    assert 99.9 < 100 * (1 - busy / length) < 100.0


def test_window_is_the_whole_span_unless_the_buffer_filled(recorded, monkeypatch):
    lo, hi = tr.window(recorded)
    span = next(s for s in recorded.spans if s[0] == "bench.window")
    assert (lo, hi) == (span[1], span[2])
    dev = recorded.devices[0]
    assert hi > max(e for _, _, e in dev.ops + dev.modules)  # idle at the end counts
    assert not tr.overflowed(recorded)
    monkeypatch.setattr(tr, "EVENT_CAP", len(dev.ops))
    assert tr.overflowed(recorded)
    lo, hi = tr.window(recorded)
    assert lo == span[1] and hi == max(e for _, _, e in dev.ops + dev.modules)


def test_longest_gap_is_a_host_sleep(recorded):
    gap = tr.longest_gap(recorded)
    # 20 ms sleeps between the programs
    assert 0.015 < gap < 0.030
    lo, hi = tr.window(recorded)
    assert gap < hi - lo


def test_gap_attribution_names_what_the_host_was_doing(recorded):
    doc = tr.breakdown(recorded)
    assert len(doc["device_ops"]) <= 10 and len(doc["idle_gaps"]) <= 10
    assert doc["device_ops"][0][0] == "%fusion fusion"
    gap_names = [name for name, _ in doc["idle_gaps"]]
    assert all(name.startswith("bench.wait") or name == tr.BETWEEN_OPS
               for name in gap_names)
    assert gap_names[0].startswith("bench.wait >")
    busy, length = tr.busy_seconds(recorded)
    assert sum(s for _, s in doc["idle_gaps"]) == pytest.approx(length - busy)


def test_short_op_name():
    line = ("%while.2946 = (s32[]{:T(128)}, f32[32,42]{1,0:T(8,128)}) "
            "while((s32[]) %tuple), condition=%c, body=%b")
    assert tr.short_op_name(line) == "%while.2946 while"
    assert tr.short_op_name("plain") == "plain"


def _snapshot(series, labels, value):
    import json
    return {series: {"series": {json.dumps(list(labels)): value}}}


def test_histogram_sum_delta_per_model():
    spec = {"reader": "histogram_sum_delta", "series": "h",
            "label_values": [["load"]]}
    record = {
        "snap_start": _snapshot("h", ["load"], {"sum": 3.0, "count": 1}),
        "snap_end": _snapshot("h", ["load"], {"sum": 9.0, "count": 3}),
        "chunk_machines": 4,
    }
    assert readers.read(spec, record) == pytest.approx(6.0 / (2 * 4))
    record["snap_end"] = record["snap_start"]
    assert readers.read(spec, record) is None  # nothing observed: left out


def test_histogram_sum_delta_adds_label_tuples():
    import json
    spec = {"reader": "histogram_sum_delta", "series": "h",
            "label_values": [["fetch"], ["assemble"]]}
    end = {"h": {"series": {json.dumps(["fetch"]): {"sum": 4.0, "count": 2},
                            json.dumps(["assemble"]): {"sum": 1.0, "count": 2}}}}
    record = {"snap_start": {}, "snap_end": end, "chunk_machines": 5}
    assert readers.read(spec, record) == pytest.approx(5.0 / (2 * 5))


@pytest.mark.parametrize("labels,expected", [
    ([["backend"]], 7.0), ([["backend"], ["trace"]], 7.0), ([["lower"]], None)])
def test_counter_delta_reads_what_set_up_gathered(labels, expected):
    spec = {"reader": "counter_delta", "series": "c", "label_values": labels}
    record = {"snap_start": _snapshot("c", ["backend"], 7.0),
              "snap_end": _snapshot("c", ["backend"], 12.0)}
    assert readers.read(spec, record) == expected
    assert readers.read({**spec, "series": "absent"}, record) is None


def test_window_rate_and_record_field():
    record = {"models": 64, "window_seconds": 47.0, "chips": 1, "setup_s": 120.5}
    rate = readers.read({"reader": "window_rate", "per_seconds": 3600}, record)
    assert rate == pytest.approx(64 / 47.0 * 3600)
    assert readers.read({"reader": "record_field", "field": "setup_s"}, record) == 120.5


def test_trace_readers_return_nothing_without_a_trace():
    assert readers.read({"reader": "trace_longest_gap"}, {"trace": None}) is None


def test_gap_and_mfu_readers(recorded):
    record = {"trace": recorded, "models": 8, "chips": 1, "window_seconds": 0.2,
              "work_per_chunk": {"flops_per_model": 197e12 * 0.2 / 8 / 4},
              "device_kind": "TPU v5 lite"}
    gap = readers.read({"reader": "trace_longest_gap"}, record)
    assert gap == tr.longest_gap(recorded)
    # a quarter of the peak over the window, by construction
    assert readers.read({"reader": "window_mfu"}, record) == pytest.approx(25.0)
    record["device_kind"] = "TPU v9 imaginary"
    with pytest.raises(KeyError):
        readers.read({"reader": "window_mfu"}, record)


HOURGLASS = dict(n_features=50, dims=(42, 33, 25, 25, 33, 42), lookback=12,
                 n_rows=13105, n_splits=3)
SYMMETRIC = dict(n_features=50, dims=(256, 128, 64, 64, 128, 256), lookback=12,
                 n_rows=13105, n_splits=3)


@pytest.mark.parametrize("shape,epochs,machines,kernel_params", [
    (HOURGLASS, 10, 32, 58512), (SYMMETRIC, 1, 24, 1096192)])
def test_flops_against_a_hand_count(shape, epochs, machines, kernel_params):
    work = flops.chunk_work(machines=machines, epochs=epochs, **shape)
    assert work["kernel_params"] == kernel_params
    # folds of 3276, 6552 and 9828 train rows, 3276, 3276 and 3277 held out
    # (the last block takes the row left over), then all rows
    trained = sum(r - 11 for r in (3276, 6552, 9828, 13105)) * epochs
    predicted = 2 * (3276 - 11) + (3277 - 11)
    assert work["trained_windows"] == trained
    assert work["predicted_windows"] == predicted
    per_model = kernel_params * 12 * (6 * trained + 2 * predicted)
    assert work["flops_per_model"] == pytest.approx(per_model)
    assert work["flops"] == pytest.approx(per_model * machines)


def test_kernel_params_by_hand_for_one_layer():
    # one LSTM layer of 3 units on 2 features, head to 2: 4*3*(2+3) + 3*2
    assert flops.kernel_params((3,), 2, 2) == 66


def test_folds_are_the_programs_expanding_blocks():
    assert flops.time_series_folds(13105, 3) == [
        (3276, 3276), (6552, 3276), (9828, 3277)]
    from benchmark.reference import lstm_ae
    assert lstm_ae.expanding_folds(13105, 3) == (
        (3276, 6552), (6552, 9828), (9828, 13105))


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks on record"):
        device.peaks("TPU v9 imaginary")
    assert device.peaks("TPU v5 lite")["flops_per_s"] == 197e12


def test_device_report_counts_a_reservation_only_as_far_as_a_program_accounts_for_it(
        monkeypatch):
    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

        def __init__(self, in_use, reserved):
            self._s = {"peak_bytes_in_use": in_use, "peak_bytes_reserved": reserved}

        def memory_stats(self):
            return self._s

    programs = [{"name": "jit_program", "temp_bytes": 80, "argument_bytes": 1,
                 "output_bytes": 1}]
    monkeypatch.setattr(device, "loaded_programs", lambda devices: programs)
    doc = device.report([Dev(5, 100), Dev(50, 70)])
    # device 0: 5 + min(100, 80); device 1: 50 + min(70, 80)
    assert doc["memory_peak_bytes"] == 120 and doc["count"] == 2
    assert doc["peak_bytes_in_use"] == 50 and doc["peak_bytes_reserved"] == 70
    assert doc["largest_program_temp_bytes"] == 80
    assert doc["platform"] == "tpu" and doc["kind"] == "TPU v5 lite"
    monkeypatch.setattr(device, "loaded_programs", lambda devices: [])
    assert device.report([Dev(5, 100)])["memory_peak_bytes"] == 5


def test_loaded_programs_lists_what_jax_holds():
    import jax
    import jax.numpy as jnp

    jax.jit(lambda x: x @ x, inline=False)(jnp.ones((4, 4)))
    programs = device.loaded_programs(jax.devices()[:1])
    assert programs and {"name", "temp_bytes", "argument_bytes", "output_bytes"} <= set(
        programs[0])
    assert programs == sorted(programs, key=lambda e: -e["temp_bytes"])
