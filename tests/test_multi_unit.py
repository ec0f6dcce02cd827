"""Multi-unit modeled time outside the serving stack: the unit clocks
and the lifted pipeline synthesis.

* ``UnitClocks`` — the one scheduling recurrence that ``Simulator`` and
  ``StagedProgram.run_pipelined`` share;
* ``synthesize``/``run_pipelined`` accept mappings that revisit a unit
  (endpoint → server → endpoint), contending for one physical clock.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import (Link, Mapping, PlatformGraph, PlatformModel,
                        ProcessingUnit, Simulator, synthesize)
from repro.core.clocks import UnitClocks

from test_core_graph import chain_graph


# ---------------------------------------------------------------------------
# UnitClocks
# ---------------------------------------------------------------------------

class TestUnitClocks:
    def test_charge_recurrence(self):
        c = UnitClocks()
        s0, f0 = c.charge("u", 0.0, 2.0)
        assert (s0, f0) == (0.0, 2.0)
        # ready before the clock: starts when the unit frees up
        s1, f1 = c.charge("u", 1.0, 1.0)
        assert (s1, f1) == (2.0, 3.0)
        # ready after the clock: the unit idles until the input lands
        s2, f2 = c.charge("u", 5.0, 1.0)
        assert (s2, f2) == (5.0, 6.0)
        assert c.makespan_s == 6.0
        assert c.busy_s["u"] == pytest.approx(4.0)  # 2 + 1 + 1, no idle

    def test_set_never_goes_backwards(self):
        c = UnitClocks()
        c.set("u", 5.0)
        c.set("u", 3.0)
        assert c.now("u") == 5.0


# ---------------------------------------------------------------------------
# lifted pipeline synthesis (a unit may appear in several segments)
# ---------------------------------------------------------------------------

class TestSynthesisRevisit:
    def _offload_mapping(self, g):
        """endpoint -> server -> endpoint: the offload shape the old
        each-unit-appears-once splitter rejected."""
        return Mapping("m", {"src": "ep", "a0": "ep", "a1": "sv",
                             "a2": "ep", "snk": "ep"})

    def test_split_opens_segment_per_revisit(self):
        g = chain_graph(3)
        prog = synthesize(g, self._offload_mapping(g))
        assert [s.unit for s in prog.stages] == ["ep", "sv", "ep"]
        assert [s.key for s in prog.stages] == ["ep", "sv", "ep#1"]
        # both boundary crossings carry a channel
        assert len(prog.channels) == 2

    def test_run_local_matches_simulator(self):
        g = chain_graph(3)
        prog = synthesize(g, self._offload_mapping(g))
        feed = np.arange(4, dtype=np.float32)
        out = prog.run_local({"src": feed})
        sim = Simulator(g).run(1, source_inputs={"src": [feed]})
        np.testing.assert_allclose(out["snk"][0], sim.outputs["snk"][0])

    def test_run_pipelined_revisits_contend_for_one_clock(self):
        g = chain_graph(3)
        for a, flops in (("a0", 1e9), ("a1", 1e9), ("a2", 1e9)):
            g.actors[a].cost_flops = flops
        pg = PlatformGraph("p")
        pg.add_unit(ProcessingUnit("ep", flops=1e9))
        pg.add_unit(ProcessingUnit("sv", flops=1e9))
        pg.add_link(Link("ep", "sv", bandwidth=1e9))
        pg.add_link(Link("sv", "ep", bandwidth=1e9))
        m = Mapping("m", {"src": "ep", "a0": "ep", "a1": "sv",
                          "a2": "ep", "snk": "ep"}, pg)
        prog = synthesize(g, m)
        frames = [{"src": np.full(4, i, np.float32)} for i in range(4)]
        sinks, sched = prog.run_pipelined(frames, platform=PlatformModel(pg))
        for i, s in enumerate(sinks):
            np.testing.assert_allclose(s["snk"][0], np.full(4, i + 3.0))
        # both ep segments charged ONE physical clock: ep busy time is
        # the sum over its two stages, and entries exist for both
        ep_entries = [e for e in sched.entries if e.unit == "ep"]
        assert len(ep_entries) == 2 * len(frames)
        assert sched.unit_busy_s["ep"] == pytest.approx(
            sum(e.finish_s - e.start_s for e in ep_entries))
        # pipelining across 2 physical units still beats sequential
        assert sched.makespan_s <= sched.sequential_s + 1e-12

    def test_same_unit_channel_carries_no_comm_bytes(self):
        """A skip connection between two segments of ONE unit (ep seg 0
        feeds both the server segment and the later ep#1 segment) is an
        in-memory hand-off: the channel exists so the data flows, but no
        modeled bytes cross a device boundary."""
        from test_core_graph import _sink, _source, _spa
        from repro.core import Graph
        g = Graph("skip")
        src = g.add_actor(_source("src"))
        a = g.add_actor(_spa("a", n_out=2, fn=lambda ts: ts[0] + 1.0))
        b = g.add_actor(_spa("b", fn=lambda ts: ts[0] * 2.0))
        c = g.add_actor(_spa("c", n_in=2, fn=lambda ts: ts[0] + ts[1]))
        snk = g.add_actor(_sink("snk"))
        g.connect(src.port("out"), a.port("in"))
        g.connect(a.port("out0"), b.port("in"))
        g.connect(a.port("out1"), c.port("in1"))
        g.connect(b.port("out"), c.port("in0"))
        g.connect(c.port("out"), snk.port("in"))
        m = Mapping("m", {"src": "ep", "a": "ep", "b": "sv",
                          "c": "ep", "snk": "ep"})
        prog = synthesize(g, m)
        assert [s.key for s in prog.stages] == ["ep", "sv", "ep#1"]
        same = [ch for ch in prog.channels if ch.src_unit == ch.dst_unit]
        cross = [ch for ch in prog.channels if ch.src_unit != ch.dst_unit]
        assert len(same) == 1 and len(cross) == 2    # the a->c skip is free
        assert prog.comm_bytes_per_iteration() == \
            sum(ch.token_bytes for ch in cross)
        # and the data still flows through the in-memory channel:
        # snk = 2*(x+1) + (x+1) = 3x+3
        feed = np.arange(4, dtype=np.float32)
        np.testing.assert_allclose(prog.run_local({"src": feed})["snk"][0],
                                   3 * feed + 3)
