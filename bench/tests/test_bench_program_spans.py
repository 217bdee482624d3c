"""The readers of the program's own spans (``client_make_ms_per_round``,
``cohort_pad_ms_per_round``) on a CPU profile of a tiny streaming run,
and on the recorded TPU traces, which predate the program's spans."""
import gzip
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (ROOT / "src", ROOT):
    sys.path.insert(0, str(_p))

from bench import trace  # noqa: E402
from bench.harness import load_module  # noqa: E402

READERS = ("client_make_ms_per_round", "cohort_pad_ms_per_round")
FIXTURES = Path(__file__).with_name("fixtures")


def _reader(name):
    return load_module(ROOT / "bench" / "metrics" / f"{name}.py")


@pytest.fixture(scope="module")
def cpu_run(tmp_path_factory):
    span_run = load_module(ROOT / "tests" / "_span_run.py")
    run = span_run.profiled_stream_run(str(tmp_path_factory.mktemp("tr")))
    run["trace"] = trace.Reduced(trace.extract(run["xplane"]))
    return run


def _span_ms(red, names, rounds):
    return sum(h[3] for h in red.host if h[1] in names) / 1e6 / rounds


def test_readers_sum_the_program_spans_of_a_cpu_run(cpu_run):
    red, rounds = cpu_run["trace"], cpu_run["rounds"]
    ctx = SimpleNamespace(trace=red, rounds=rounds)
    make = _reader("client_make_ms_per_round").read(ctx)
    pad = _reader("cohort_pad_ms_per_round").read(ctx)
    assert make > 0 and pad > 0
    # every span lies inside the window, so clipping cuts nothing
    assert make == pytest.approx(_span_ms(red, {"cohort.make"}, rounds))
    assert pad == pytest.approx(
        _span_ms(red, {"cohort.pad", "stream.pad"}, rounds))
    fetch = _span_ms(red, {"cohort.fetch"}, rounds)
    assert make < fetch


@pytest.mark.parametrize("tag", ["femnist_logreg", "synthetic_1_1_logreg"])
@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_on_traces_without_program_spans(tag, name):
    with gzip.open(FIXTURES / f"trace_{tag}.json.gz", "rt") as f:
        fx = json.load(f)
    ctx = SimpleNamespace(trace=trace.Reduced(fx),
                          rounds=fx["expect"]["rounds"])
    assert _reader(name).read(ctx) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_without_a_trace(name):
    assert _reader(name).read(SimpleNamespace(trace=None, rounds=10)) \
        is None
