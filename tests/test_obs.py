"""Host spans (``repro.obs``): what a recorder keeps, and the spans the
episode layer and the router's entry emit."""
import glob

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import batch_router as br
from repro.core.catalog import build_catalog
from repro.launch.serve import make_multicell_fleet
from repro.workloads import compile_scenario, get_scenario, simulate

EDGE_ARCHS = ["smollm_135m", "starcoder2_3b", "mamba2_2p7b",
              "musicgen_medium"]


@pytest.fixture(scope="module")
def tiny():
    catalog = build_catalog(EDGE_ARCHS)
    fleet = make_multicell_fleet(2, 3, catalog, drain_rate=50.0)
    params, state = br.fleet_from_servers(fleet, catalog)
    reqs = compile_scenario(get_scenario("steady", num_requests=200), seed=3,
                            num_models=len(catalog), num_cells=2)
    return params, state, reqs, len(fleet) - 1


def _children(rec, i):
    return [s for s in rec.spans if s.parent == i]


def test_nesting_parents_and_counts():
    with obs.recording() as rec:
        with obs.span("a", requests=3):
            with obs.span("a.b"):
                pass
            with obs.span("a.c", windows=2):
                with obs.span("a.c.d"):
                    pass
        with obs.span("e"):
            pass
    names = [s.name for s in rec.spans]
    assert names == ["a", "a.b", "a.c", "a.c.d", "e"]
    assert [s.parent for s in rec.spans] == [None, 0, 0, 2, None]
    assert rec.spans[0].counts == {"requests": 3}
    assert rec.spans[2].counts == {"windows": 2}
    for s in rec.spans:
        assert 0.0 <= s.seconds
    a, b, c, d, _ = rec.spans
    assert a.start_s <= b.start_s <= b.end_s <= c.start_s <= d.start_s \
        <= d.end_s <= c.end_s <= a.end_s


def test_without_a_recorder_nothing_is_kept():
    with obs.recording() as rec:
        pass
    with obs.span("after"):  # the recorder's block has ended
        pass
    assert rec.spans == []
    with obs.recording() as outer:
        with obs.recording() as inner:
            with obs.span("x"):
                pass
        with obs.span("y"):
            pass
    assert [s.name for s in inner.spans] == ["x"]
    assert [s.name for s in outer.spans] == ["y"]


def test_a_span_is_closed_when_its_block_raises():
    with obs.recording() as rec:
        with pytest.raises(ValueError):
            with obs.span("boom"):
                raise ValueError
        with obs.span("next"):
            pass
    assert np.isfinite(rec.spans[0].end_s)
    assert rec.spans[1].parent is None


def test_simulate_spans_one_episode(tiny):
    params, state, reqs, cloud = tiny
    w = 64  # 200 requests: windows of 64, 64, 64 and 8
    with obs.recording() as rec:
        simulate(params, state, reqs, window_requests=w, chunk=16,
                 cloud_index=cloud)
    tops = [i for i, s in enumerate(rec.spans) if s.parent is None]
    assert [rec.spans[i].name for i in tops] == ["repro.simulate"]
    top = rec.spans[tops[0]]
    assert top.counts == {"requests": 200, "windows": 4}
    kids = [s.name for s in _children(rec, tops[0])]
    # one-deep pipeline: window i's wait and sample follow window i+1's
    # dispatch, and only the last window's read-back has nothing behind it
    read_back = ["repro.simulate.wait", "repro.simulate.sample"]
    assert kids == (["repro.simulate.window"] * 2 + read_back
                    + (["repro.simulate.window"] + read_back) * 2
                    + read_back + ["repro.simulate.stats"])
    samples = [s for s in rec.spans if s.name == "repro.simulate.sample"]
    assert [s.counts["in_flight"] for s in samples] == [1, 1, 1, 0]
    routes = [s for s in rec.spans if s.name == "repro.route"]
    assert [s.counts["requests"] for s in routes] == [64, 64, 64, 8]
    for s in routes:
        assert rec.spans[s.parent].name == "repro.simulate.window"
    assert all(top.start_s <= s.start_s and s.end_s <= top.end_s
               for s in rec.spans)


def test_route_batch_gives_one_span_a_call(tiny):
    params, state, reqs, _ = tiny
    with obs.recording() as rec:
        for _ in range(3):
            state, _ = br.route_batch(params, state, reqs)
    assert [(s.name, s.parent, s.counts) for s in rec.spans] == \
        [("repro.route", None, {"requests": 200})] * 3


def test_spans_reach_the_profiler_with_their_counts(tiny, tmp_path):
    params, state, reqs, cloud = tiny
    simulate(params, state, reqs, window_requests=100, cloud_index=cloud)
    with jax.profiler.trace(str(tmp_path)):
        simulate(params, state, reqs, window_requests=100, cloud_index=cloud)
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    got = [(e.name, dict(e.stats)) for p in pd.planes
           if p.name.startswith("/host:") for line in p.lines
           for e in line.events if e.name.startswith("repro.")]
    names = [n for n, _ in got]
    assert names.count("repro.simulate") == 1
    assert names.count("repro.route") == 2
    assert names.count("repro.simulate.wait") == 2
    assert dict(got)["repro.simulate"] == {"requests": 200, "windows": 2}
