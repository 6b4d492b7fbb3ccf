"""Prometheus exposition conformance (obs/promexport.py, ISSUE 8).

A strict mini-parser for the text format 0.0.4 validates what a real
scraper would enforce: HELP/TYPE grammar, legal metric names, histogram
buckets cumulative with the ``+Inf`` terminal and ``_count == +Inf``,
``_sum`` present — then the round-trip: every eligible name in the
agent's live ``/metrics`` JSON snapshot appears in the exposition, with
the exact negotiated content-type.
"""

import asyncio
import re

import pytest

from ai_rtc_agent_tpu.obs.promexport import CONTENT_TYPE, labeled, render
from ai_rtc_agent_tpu.obs.slo import SloPlane
from ai_rtc_agent_tpu.obs.trace import STAGES, SessionTracer, TraceController

NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
HELP_RE = re.compile(r"^# HELP ([a-zA-Z_:][a-zA-Z0-9_:]*) (.*)$")
TYPE_RE = re.compile(
    r"^# TYPE ([a-zA-Z_:][a-zA-Z0-9_:]*) "
    r"(counter|gauge|histogram|summary|untyped)$"
)
SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{((?:[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\]|\\.)*\",?)*)\})?"
    r" (-?(?:[0-9]*\.?[0-9]+(?:[eE][+-]?[0-9]+)?)|[+-]Inf|NaN)$"
)
LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def validate_exposition(text: str) -> dict:
    """Parse + conformance-check; returns {family: {"type", "samples"}}
    where samples is [(name, labels-dict, float value)]."""
    assert text.endswith("\n"), "exposition must end with a newline"
    families: dict = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            m = HELP_RE.match(line)
            assert m, f"malformed HELP line: {line!r}"
            continue
        if line.startswith("# TYPE "):
            m = TYPE_RE.match(line)
            assert m, f"malformed TYPE line: {line!r}"
            name, kind = m.groups()
            assert name not in families, f"duplicate TYPE for {name}"
            families[name] = {"type": kind, "samples": []}
            continue
        assert not line.startswith("#"), f"unknown comment: {line!r}"
        m = SAMPLE_RE.match(line)
        assert m, f"malformed sample line: {line!r}"
        name, labels_raw, value = m.group(1), m.group(2), m.group(3)
        labels = dict(LABEL_RE.findall(labels_raw)) if labels_raw else {}
        base = name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[: -len(suffix)] in families:
                if families[name[: -len(suffix)]]["type"] == "histogram":
                    base = name[: -len(suffix)]
        assert base in families, f"sample {name} has no TYPE declaration"
        families[base]["samples"].append((name, labels, float(value)))

    # histogram-family invariants
    for fam, info in families.items():
        if info["type"] != "histogram":
            continue
        series: dict = {}
        sums, counts = {}, {}
        for name, labels, value in info["samples"]:
            key = tuple(sorted(
                (k, v) for k, v in labels.items() if k != "le"
            ))
            if name == f"{fam}_bucket":
                assert "le" in labels, f"{fam} bucket without le"
                series.setdefault(key, []).append((labels["le"], value))
            elif name == f"{fam}_sum":
                sums[key] = value
            elif name == f"{fam}_count":
                counts[key] = value
            else:
                raise AssertionError(f"stray sample {name} in {fam}")
        assert series, f"histogram {fam} has no buckets"
        for key, buckets in series.items():
            les = [le for le, _ in buckets]
            assert les[-1] == "+Inf", f"{fam}{dict(key)} missing +Inf"
            bounds = [float("inf") if le == "+Inf" else float(le)
                      for le in les]
            assert bounds == sorted(bounds), f"{fam} le order"
            values = [v for _, v in buckets]
            assert values == sorted(values), (
                f"{fam}{dict(key)} buckets not cumulative: {values}"
            )
            assert key in counts, f"{fam}{dict(key)} missing _count"
            assert counts[key] == values[-1], (
                f"{fam}{dict(key)}: _count != +Inf bucket"
            )
            assert key in sums, f"{fam}{dict(key)} missing _sum"
    return families


def _slo_with_data():
    plane = SloPlane()
    ctrl = TraceController()
    ctrl.stop()
    tracer = SessionTracer("s", ctrl, slo=plane)

    class F:
        pass

    for i in range(20):
        f = F()
        tr = tracer.attach(f)
        tr.add_span("decode", 0.0, 0.002)
        tr.add_span("engine_step", 0.0, 0.02 if i % 2 else 0.2)
        tr.finish("sent")
    return plane


# -- renderer unit conformance ----------------------------------------------

def test_render_scalars_types_and_skips():
    text = render({
        "fps": 29.5,
        "frames_total": 100,
        "supervisor_degraded_total": 2,
        "trace_enabled": True,        # bool -> 0/1
        "latency_p50_ms": None,       # no data -> absent series
        "overload_queues": {"a": 1},  # nested -> JSON-only
        "host_plane_sessions": {},
        "some_list": [1, 2],
        "bad name!": 3,               # invalid name -> never emitted
    })
    fams = validate_exposition(text)
    assert fams["fps"]["type"] == "gauge"
    assert fams["frames_total"]["type"] == "counter"
    assert fams["supervisor_degraded_total"]["type"] == "counter"
    assert fams["trace_enabled"]["samples"][0][2] == 1.0
    assert "latency_p50_ms" not in fams
    assert "overload_queues" not in fams
    assert all(NAME_RE.match(f) for f in fams)


def test_render_slo_histograms_conform():
    plane = _slo_with_data()
    text = render({}, slo=plane)
    fams = validate_exposition(text)
    hist = fams["slo_stage_latency_ms"]
    assert hist["type"] == "histogram"
    stages_seen = {
        labels["stage"]
        for name, labels, _ in hist["samples"]
        if name.endswith("_bucket")
    }
    # label values come ONLY from the closed STAGES enum — every
    # budgeted stage is emitted (a fixed series set, the cardinality
    # contract); the hops below a stage have no histogram
    assert stages_seen == set(plane.budgets_ms) < set(STAGES)
    assert fams["slo_stage_budget_ms"]["type"] == "gauge"
    assert fams["slo_stage_over_budget_total"]["type"] == "counter"
    # the over-budget counter agrees with the fed data (10 of 20 over)
    over = {
        labels["stage"]: v
        for _, labels, v in fams["slo_stage_over_budget_total"]["samples"]
    }
    assert over["engine_step"] == 10.0
    assert over["decode"] == 0.0


def test_render_disabled_slo_omits_histograms():
    plane = _slo_with_data()
    plane.enabled = False
    text = render({"fps": 1.0}, slo=plane)
    assert "slo_stage_latency_ms" not in text


def test_labeled_escapes():
    line = labeled("m", {"stage": 'a"b\\c'}, 1)
    assert line == 'm{stage="a\\"b\\\\c"} 1'


# -- the agent round-trip ----------------------------------------------------

async def _with_agent_client(fn):
    from aiohttp.test_utils import TestClient, TestServer

    from ai_rtc_agent_tpu.server.agent import build_app
    from ai_rtc_agent_tpu.server.signaling import LoopbackProvider

    class Pipe:
        def __call__(self, frame):
            return 255 - frame

        def restart(self):
            pass

    app = build_app(pipeline=Pipe(), provider=LoopbackProvider())
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        return await fn(client)
    finally:
        await client.close()


def test_metrics_prom_roundtrips_every_json_name():
    async def grab(client):
        r_json = await client.get("/metrics")
        assert r_json.status == 200
        j = await r_json.json()
        r_prom = await client.get("/metrics?format=prom")
        assert r_prom.status == 200
        return j, r_prom.headers["Content-Type"], await r_prom.text()

    j, ctype, text = asyncio.run(_with_agent_client(grab))
    assert ctype == CONTENT_TYPE == "text/plain; version=0.0.4; charset=utf-8"
    fams = validate_exposition(text)
    # every eligible JSON name (numeric scalar, valid grammar) round-trips
    for key, value in j.items():
        if value is None or isinstance(value, (dict, list, str)):
            continue
        assert key in fams, f"/metrics name {key} missing from exposition"
        kind = "counter" if key.endswith("_total") else "gauge"
        assert fams[key]["type"] == kind
        assert fams[key]["samples"][0][2] == pytest.approx(float(value))
    # and the SLO histograms ride along as genuine histogram families
    assert fams["slo_stage_latency_ms"]["type"] == "histogram"


def test_metrics_unknown_format_is_400():
    async def grab(client):
        return (await client.get("/metrics?format=xml")).status

    assert asyncio.run(_with_agent_client(grab)) == 400
