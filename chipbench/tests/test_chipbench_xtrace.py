"""The reduction of a profiler trace, on synthetic traces."""

import pytest

from chipbench import xtrace


def xspace(device_events, marks=(), devices=1):
    """A text-proto XSpace: per device an XLA Modules line and an XLA Ops
    line from ``device_events`` = [(line, name, start_ns, dur_ns)], and
    host annotations ``marks`` = [(name, start_ns)]."""
    names = sorted({n for _, n, _, _ in device_events})
    ids = {n: i + 1 for i, n in enumerate(names)}
    meta = "".join(f'  event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}\n' for n, i in ids.items())
    out = ""
    for dev in range(devices):
        lines = ""
        for li, line in enumerate((xtrace.MODULES, xtrace.OPS,
                                   xtrace.ASYNC)):
            evs = "".join(
                f"    events {{ metadata_id: {ids[n]} offset_ps: {s * 1000}"
                f" duration_ps: {d * 1000} }}\n"
                for ln, n, s, d in device_events if ln == line)
            lines += (f'  lines {{ id: {li + 1} name: "{line}" '
                      f"timestamp_ns: 0\n{evs}  }}\n")
        out += (f'planes {{ id: {dev + 1} name: "/device:TPU:{dev}"\n'
                f"{lines}{meta}}}\n")
    # a device plane with no events (a part of the chip this run left idle)
    out += 'planes { id: 50 name: "/device:TPU_NON_CORE:0" }\n'
    hm = {n: i + 1 for i, (n, _) in enumerate(marks)}
    hev = "".join(f"    events {{ metadata_id: {hm[n]} offset_ps: {s * 1000}"
                  f" duration_ps: 1000 }}\n" for n, s in marks)
    hmeta = "".join(f'  event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{n}" }} }}\n' for n, i in hm.items())
    out += (f'planes {{ id: 99 name: "/host:CPU"\n  lines {{ id: 1 '
            f'name: "python" timestamp_ns: 0\n{hev}  }}\n{hmeta}}}\n')
    return out


def load(text):
    from jax.profiler import ProfileData

    return xtrace.from_profile(ProfileData.from_text_proto(text))


EVENTS = [
    # a prefill from 100 to 400 ns with two ops, a macro-step 500 to 900
    (xtrace.MODULES, "jit_prefill_fn(1)", 100, 300),
    (xtrace.OPS, "fusion.1", 100, 200),
    (xtrace.OPS, "fusion.2", 300, 100),
    (xtrace.MODULES, "jit_macro_fn(2)", 500, 400),
    (xtrace.OPS, "fusion.1", 500, 150),
    (xtrace.OPS, "convolution.3", 650, 250),
]


def test_busy_union_idle_and_modules():
    devices, marks = load(xspace(EVENTS, [(xtrace.OPEN, 0),
                                          (xtrace.CLOSE, 1000)]))
    s = xtrace.summarize(devices, marks)
    assert s.window_s == pytest.approx(1000e-9)
    # ops cover 100-400 and 500-900: 700 ns busy
    assert s.busy_s == pytest.approx(700e-9)
    assert s.module_s == pytest.approx({"prefill": 300e-9, "macro": 400e-9})
    assert s.module_count == {"prefill": 1, "macro": 1}
    gaps = sorted(d for _, d in s.idle_gaps)
    assert gaps == pytest.approx([100e-9, 100e-9, 100e-9])
    ops = dict(s.top_ops)
    assert ops["prefill/fusion.1"] == pytest.approx(200e-9)
    assert ops["macro/fusion.1"] == pytest.approx(150e-9)
    assert ops["macro/convolution.3"] == pytest.approx(250e-9)


def test_events_are_clipped_to_the_marks():
    devices, marks = load(xspace(EVENTS, [(xtrace.OPEN, 200),
                                          (xtrace.CLOSE, 700)]))
    s = xtrace.summarize(devices, marks,
                         label=lambda a, b: f"{a * 1e9:.0f}-{b * 1e9:.0f}")
    assert s.window_s == pytest.approx(500e-9)
    # busy: 200-300 (fusion.1 cut), 300-400, 500-700
    assert s.busy_s == pytest.approx(400e-9)
    assert s.module_s["prefill"] == pytest.approx(200e-9)
    assert s.module_s["macro"] == pytest.approx(200e-9)
    # the one gap, 400-500 ns, labelled in seconds after the window opened
    assert s.idle_gaps == [("200-300", pytest.approx(100e-9))]


def test_overlapping_ops_count_once_and_devices_average():
    evs = [(xtrace.OPS, "a", 0, 600), (xtrace.OPS, "b", 200, 600),
           (xtrace.MODULES, "jit_macro_fn", 0, 800)]
    devices, marks = load(xspace(evs, [(xtrace.OPEN, 0),
                                       (xtrace.CLOSE, 1000)], devices=2))
    s = xtrace.summarize(devices, marks)
    assert len(devices) == 2
    assert s.busy_s == pytest.approx(800e-9)  # per device, averaged
    assert s.module_s["macro"] == pytest.approx(1600e-9)  # summed


def test_union():
    assert xtrace.union_ns([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [(0, 3), (5, 8)]


def test_module_kind():
    assert xtrace.module_kind("jit_macro_fn(123)") == "macro"
    assert xtrace.module_kind("jit_prefill_fn") == "prefill"
    assert xtrace.module_kind("jit__reset") == "other"


def test_async_copies_count_as_busy_and_empty_planes_are_skipped():
    evs = [(xtrace.MODULES, "jit_macro_fn", 0, 1000),
           (xtrace.OPS, "fusion.1", 0, 300),
           (xtrace.OPS, "fusion.2", 700, 300),
           (xtrace.ASYNC, "slice-start.1", 200, 600)]
    devices, marks = load(xspace(evs, [(xtrace.OPEN, 0),
                                       (xtrace.CLOSE, 1000)]))
    assert [d.name for d in devices] == ["/device:TPU:0"]
    s = xtrace.summarize(devices, marks)
    assert s.busy_s == pytest.approx(1000e-9)
    assert s.idle_gaps == []


def test_short_names():
    assert xtrace.short("%fusion.169 = bf16[2,8192]{1,0:T(2,128)} fusion("
                        "bf16[32,3072,8192]{2,1,0} %x), kind=kOutput") == \
        "fusion.169 = bf16[2,8192]"
    assert xtrace.short("jit_macro_fn(123)") == "jit_macro_fn(123)"


def test_loops_are_left_out_of_the_top_ops():
    evs = [(xtrace.MODULES, "jit_macro_fn", 0, 1000),
           (xtrace.OPS, "while.1", 0, 1000),
           (xtrace.OPS, "fusion.1", 0, 400),
           (xtrace.OPS, "fusion.2", 400, 600)]
    devices, marks = load(xspace(evs, [(xtrace.OPEN, 0),
                                       (xtrace.CLOSE, 1000)]))
    s = xtrace.summarize(devices, marks)
    assert dict(s.top_ops) == pytest.approx(
        {"macro/fusion.1": 400e-9, "macro/fusion.2": 600e-9})
    assert s.busy_s == pytest.approx(1000e-9)
