"""Spans and name scopes inside the federated round.

Host: the streaming driver's five stage spans (``stream.*``), siblings
on the calling thread once per chunk, and the data plan's
``cohort.fetch`` / ``cohort.make`` / ``cohort.pad`` spans nested in
them; read from a profile of a tiny streaming run.  Device: each stage
of the round (``phase_a`` ... ``server_step``) and of the chunk bodies
(``gather``, ``eval``) as a name scope in the lowered chunk programs.
"""
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from _span_run import WINDOW, config, profiled_stream_run, selections
from repro.core.engine import make_scanned_run
from repro.data import make_synthetic_stream
from repro.models.param import init_params
from repro.models.small import logreg_loss, logreg_specs

STAGES = ("stream.schedule", "stream.cohorts", "stream.pad",
          "stream.dispatch", "stream.readback")
ROUND_SCOPES = ("phase_a", "correction", "local_solve", "aggregate",
                "server_step", "eval")


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    run = profiled_stream_run(str(tmp_path_factory.mktemp("trace")))
    host = []
    for plane in ProfileData.from_file(run["xplane"]).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                host += [(line.name, e.name, int(e.start_ns),
                          int(e.start_ns + e.duration_ns))
                         for e in line.events]
    window = next(h for h in host if h[1] == WINDOW)
    run["thread"] = window[0]
    run["host"] = sorted(host, key=lambda h: (h[2], -h[3]))
    return run


def _spans(run, *names):
    return [h for h in run["host"] if h[1] in names]


def test_stream_stages_are_ordered_siblings_on_the_calling_thread(
        profiled):
    stages = _spans(profiled, *STAGES)
    assert {h[0] for h in stages} == {profiled["thread"]}
    chunks = profiled["rounds"] // profiled["driver"].cfg.chunk_rounds
    assert [h[1] for h in stages] == list(STAGES) * chunks
    for a, b in zip(stages, stages[1:]):
        assert a[3] <= b[2], (a, b)          # none encloses the next


def test_one_make_span_per_generated_client(profiled):
    makes = _spans(profiled, "cohort.make")
    assert profiled["made"] > 0
    assert len(makes) == profiled["made"]
    fetches = _spans(profiled, "cohort.fetch")
    for m in makes:
        assert any(f[2] <= m[2] and m[3] <= f[3] for f in fetches), m


def test_cohort_pad_never_nests_in_stream_pad(profiled):
    pads = _spans(profiled, "cohort.pad")
    cohorts = _spans(profiled, "stream.cohorts")
    stream_pads = _spans(profiled, "stream.pad")
    assert pads and stream_pads
    for p in pads:
        assert not any(s[2] <= p[2] < s[3] for s in stream_pads), p
        assert any(c[2] <= p[2] and p[3] <= c[3] for c in cohorts), p


def _chunk_text(client_source: str) -> str:
    """The lowered text, with locations, of the chunk program a small
    run of ``client_source``'s plan dispatches.  The server takes a
    momentum step: plain averaging leaves ``server_step`` without ops."""
    src = make_synthetic_stream(1.0, 1.0, num_devices=12, seed=3)
    drv = make_scanned_run(logreg_loss, src, config(
        num_devices=12, client_source=client_source,
        server_opt="momentum"))
    params = init_params(logreg_specs(60, 10), jax.random.PRNGKey(0))
    attr = "_chunk_stream" if drv.streaming else "_chunk_injected"
    jitted, seen = getattr(drv, attr), []

    def record(*args):
        seen.append(jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype), args))
        return jitted(*args)

    setattr(drv, attr, record)
    try:
        drv.run(params, 2, selections=selections(np.arange(12), 2))
    finally:
        setattr(drv, attr, jitted)
    return jitted.lower(*seen[0]).as_text(debug_info=True)


@pytest.mark.parametrize("client_source", ["streaming", "stacked"])
def test_chunk_programs_carry_the_stage_scopes(client_source):
    text = _chunk_text(client_source)
    # a location names the op last, after the scopes it was traced in
    scopes = {part for loc in re.findall(r'loc\("([^"]*)"', text)
              for part in loc.split("/")[:-1]}
    for scope in ROUND_SCOPES:
        assert scope in scopes, scope
    # only the stacked plan gathers its cohorts inside the program
    assert ("gather" in scopes) == (client_source == "stacked")
