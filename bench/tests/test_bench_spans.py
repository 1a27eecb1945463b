"""The readers of the program's own spans, on synthetic span lists:
nested spans and both parties' overlapping spans are counted once, the
online and offline phases are kept apart, and a window without the
spans reads nothing."""

import importlib.util
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import spans as S

METRICS = Path(__file__).resolve().parents[1] / "metrics"
#: each reader: its phase and a span it reads
READERS = {
    "ot_s": ("online", "ot.extend"),
    "share_plane_s": ("online", "share.matmul"),
    "gc_upload_s": ("online", "gc.upload"),
    "gc_fetch_s.online": ("online", "gc.fetch"),
    "gc_fetch_s.offline": ("offline", "gc.fetch"),
    "table_codec_s": ("offline", "tables.unpack"),
    "he_offline_s": ("offline", "he.offline"),
}
MS = 1_000_000  # ns


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def span(name, path, t0, t1, tid=1, **attrs):
    return SimpleNamespace(name=name, path=path, t0_ns=t0 * MS,
                           t1_ns=None if t1 is None else t1 * MS,
                           _tid=tid, attrs=attrs)


def ctx(phase, spans, units=2):
    return SimpleNamespace(window=SimpleNamespace(phase=phase),
                           units=units, spans=spans)


def test_covered_counts_each_instant_once():
    spans = [span("a", "online/a", 0, 10), span("a", "online/a/a", 2, 5),
             span("a", "online/a", 8, 14, tid=2),
             span("a", "online/a", 20, 21)]
    assert S.covered_s(spans) == pytest.approx(15e-3)
    assert S.covered_s([]) == 0.0


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_counts_nested_and_concurrent_spans_once(name):
    phase, sp = READERS[name]
    spans = [span("op:trunc", f"{phase}/op:trunc", 0, 100),
             span(sp, f"{phase}/op:trunc/{sp}", 10, 40),
             # nested in the first, and one on the other party's thread
             span(sp, f"{phase}/op:trunc/{sp}/{sp}", 15, 20),
             span(sp, f"{phase}/op:trunc/{sp}", 30, 50, tid=2),
             span(sp, f"{phase}/op:trunc/{sp}", 60, None)]  # unfinished
    # 10..50 ms over 2 units
    assert reader(name)(ctx(phase, spans)) == pytest.approx(20e-3)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_keeps_the_phases_apart(name):
    phase, sp = READERS[name]
    other = "offline" if phase == "online" else "online"
    spans = [span(sp, f"{other}/op:trunc/{sp}", 0, 10),
             span(sp, f"{phase}/x/{sp}", 20, 23)]
    assert reader(name)(ctx(phase, spans, units=1)) == pytest.approx(3e-3)
    # a window of the other phase reads nothing, whatever spans it holds
    assert reader(name)(ctx(other, spans, units=1)) is None


@pytest.mark.parametrize("name", sorted(READERS) + ["gc_upload_mb"])
def test_reader_without_its_spans_reads_nothing(name):
    phase = READERS.get(name, ("online",))[0]
    assert reader(name)(ctx(phase, [])) is None
    assert reader(name)(ctx(phase, [span("op:linear", f"{phase}/op:linear",
                                         0, 5)])) is None
    assert reader(name)(ctx(phase, [], units=0)) is None


def test_upload_megabytes_sum_the_byte_counters():
    spans = [span("gc.upload", "online/op:trunc/gc.upload", 0, 1,
                  bytes=3_000_000),
             span("gc.upload", "online/op:gc_apply/gc.upload", 2, 3,
                  bytes=1_000_000),
             span("gc.upload", "offline/x/gc.upload", 4, 5, bytes=7)]
    assert reader("gc_upload_mb")(ctx("online", spans)) == pytest.approx(2.0)
    assert reader("gc_upload_mb")(ctx("offline", spans)) is None


def test_table_codec_reads_both_parties_codec_spans():
    spans = [span("tables.pack", "offline/slab/tables.pack", 0, 4),
             span("tables.unpack", "offline/gateway.prep/tables.unpack",
                  10, 13, tid=2),
             span("labels.stream", "offline/gateway.prep/labels.stream",
                  13, 14, tid=2),
             span("he.offline", "offline/gateway.prep/he.offline", 14, 30,
                  tid=2)]
    assert reader("table_codec_s")(ctx("offline", spans, units=1)) == \
        pytest.approx(8e-3)
