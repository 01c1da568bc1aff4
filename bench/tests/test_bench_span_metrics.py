"""The readers of the dispatcher's spans, on a hand-built run whose spans
have known durations: two buckets in the window, a third outside it,
admission spans on a client thread, and runs of a program that records
none of these spans."""
import numpy as np
import pytest

from bench import spec
from bench.record import Run

T0, T1 = 100.0, 110.0
#: one bucket's top-level dispatcher phases, ms
PHASES = {"wait": 2.0, "select": 0.1, "gather": 0.4, "put": 1.0,
          "launch": 0.5, "fetch": 6.0, "fold": 2.0, "resolve": 1.0}
#: its blocking reads: (parent, ms each, how many)
SYNCS = [("fetch", 1.0, 5), ("fold", 0.5, 3)]
NEW = ("bucket_host_ms", "bucket_sync_ms", "bucket_wait_ms",
       "syncs_per_bucket")


def _bucket(bid, start):
    """The spans of bucket ``bid``, phases back to back from ``start``."""
    out, t = [{"name": "bucket", "ts": start + 0.0021, "dur": 0.0,
               "bid": bid, "rids": [bid], "n_samples": 8}], start
    for name, ms in PHASES.items():
        out.append({"name": name, "ts": t, "dur": ms / 1e3, "bid": bid,
                    "parent": None, "thread": "AsyncDartServer"})
        for parent, each, n in SYNCS:
            if parent == name:
                out += [{"name": "sync", "ts": t + k * each / 1e3,
                         "dur": each / 1e3, "bid": bid, "parent": name,
                         "site": "output", "thread": "AsyncDartServer"}
                        for k in range(n)]
        t += ms / 1e3
    return out


def _admit(rid, start, ms):
    """An admission on a client thread, with its put and sync children."""
    return [{"name": "admit", "ts": start, "dur": ms / 1e3, "rid": rid,
             "parent": None, "thread": "client"},
            {"name": "put", "ts": start, "dur": ms / 4e3, "parent": "admit",
             "thread": "client"},
            {"name": "sync", "ts": start + ms / 4e3, "dur": ms / 2e3,
             "parent": "admit", "site": "admit_alpha", "thread": "client"}]


def _run(spans):
    return Run(cell=None, t0=T0, t1=T1, requests=[], setup_s=0.0,
               device={}, tau=np.zeros(3), spans=spans)


def _read(name, run):
    return spec.reader(name)(run)


@pytest.fixture
def run():
    spans = _bucket(0, T0 + 1.0) + _bucket(1, T0 + 2.0) \
        + _bucket(2, T1 + 1.0)                      # after the window
    spans += _admit(0, T0 + 0.5, 3.0) + _admit(1, T0 + 0.6, 5.0) \
        + _admit(2, T0 - 1.0, 100.0)                # before the window
    return _run(spans)


def test_each_reader_reads_its_known_value(run):
    sync = sum(each * n for _, each, n in SYNCS)
    work = sum(ms for k, ms in PHASES.items() if k != "wait")
    assert _read("bucket_sync_ms", run) == pytest.approx(sync)
    assert _read("bucket_host_ms", run) == pytest.approx(work - sync)
    assert _read("bucket_wait_ms", run) == pytest.approx(PHASES["wait"])
    assert _read("syncs_per_bucket", run) == pytest.approx(8.0)
    assert _read("admit_ms", run) == pytest.approx(4.0)


def test_host_sync_and_wait_sum_to_the_bucket_cycle(run):
    total = sum(_read(n, run) for n in NEW[:3])
    assert total == pytest.approx(sum(PHASES.values()))


def test_spans_of_a_program_without_them_read_none():
    """The request spans a program recorded before the phase spans
    existed: the dispatcher readers read None; admission still reads."""
    spans = [{"name": "bucket", "ts": T0 + 1.0, "dur": 0.0, "n_samples": 8},
             {"name": "admit", "ts": T0 + 0.5, "dur": 0.002, "rid": 0},
             {"name": "queue_wait", "ts": T0 + 0.5, "dur": 0.5, "rid": 0},
             {"name": "compiled_step", "ts": T0 + 1.0, "dur": 0.03,
              "rid": 0}]
    for name in NEW:
        assert _read(name, _run(spans)) is None, name
    assert _read("admit_ms", _run(spans)) == pytest.approx(2.0)


@pytest.mark.parametrize("name", NEW + ("admit_ms",))
def test_an_untraced_or_empty_run_reads_none(name):
    assert _read(name, _run(None)) is None
    assert _read(name, _run([])) is None


def test_phases_without_a_bucket_read_none():
    spans = [s for s in _bucket(0, T0 + 1.0) if s["name"] != "bucket"]
    for name in NEW:
        assert _read(name, _run(spans)) is None, name


def test_a_quiet_window_reads_zero_not_none():
    """A dispatcher that never waited, or whose phases made no blocking
    read, reads 0, not None."""
    spans = [s for s in _bucket(0, T0 + 1.0) if s["name"] != "sync"]
    assert _read("syncs_per_bucket", _run(spans)) == 0.0
    assert _read("bucket_sync_ms", _run(spans)) == 0.0
    busy = [s for s in _bucket(0, T0 + 1.0) if s["name"] != "wait"]
    assert _read("bucket_wait_ms", _run(busy)) == 0.0
    assert _read("bucket_host_ms", _run(busy)) == pytest.approx(
        sum(PHASES.values()) - PHASES["wait"]
        - sum(each * n for _, each, n in SYNCS))


def test_syncs_count_by_the_bucket_they_belong_to():
    """A bucket dispatched at the end of the window is finished after
    it: its reads still count to it; a bucket not yet finished when the
    spans were read is left out."""
    late = _bucket(1, T1 - 0.003)                 # fetch after the window
    unfinished = [s for s in _bucket(2, T1 - 0.0025)
                  if s["name"] not in ("sync", "fetch", "fold", "resolve")]
    for spans in (late, unfinished):
        bucket = next(s for s in spans if s["name"] == "bucket")
        assert T0 <= bucket["ts"] <= T1
    assert all(s["ts"] > T1 for s in late if s["name"] == "sync")
    spans = _bucket(0, T0 + 1.0) + late + unfinished
    assert _read("syncs_per_bucket", _run(spans)) == pytest.approx(8.0)
