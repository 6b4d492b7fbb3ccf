"""What ``BENCHMARK.json`` names, found on disk.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by its name: a later PR adds
files and entries and edits nothing here.

  configuration  <paths[0]>/configs/<name>.json     (its ``file`` entry)
  traffic mix    <paths[0]>/traffic/<traffic>.json
  layer metric   <paths[0]>/layer_metrics/<name>.py  with ``read(ctx)``
  reference      <paths[0]>/reference/<name>.py  (the configuration file's
                 ``reference``) with ``weight_shapes(cfg)`` and
                 ``Reference(cfg, weights)``
  operations     <paths[0]>/flops/<name>.py  (the configuration file's
                 ``flops``) with ``frame_flops(cfg)`` and what a kernel's
                 roofline reader asks of it

A second model family came in that way (``tests/data/configs/tinyxl64.json``
with ``reference/sdxl_stream.py`` and ``flops/sdxl_stream.py``: two text
towers, an addition embedding).  What a configuration file may state beside
its sizes, each with a default that the first family's files rely on:
``program_text_subtrees`` (the top-level subtrees of the weight tree that the
program's ``encode_prompt`` reads: ``["clip"]``), ``program_stream_overrides``
(none) and ``weights.rules`` (none).

A name the disk lacks is an error that says which.
"""

from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class MissingPiece(FileNotFoundError):
    pass


def _read_json(path: str, what: str):
    if not os.path.isfile(path):
        raise MissingPiece(f"{what}: no file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


class Benchmark:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.spec = _read_json(os.path.join(root, "BENCHMARK.json"), "BENCHMARK.json")
        self.home = os.path.join(root, self.spec["paths"][0])

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        known = ", ".join(w["name"] for w in self.spec["workloads"])
        raise MissingPiece(f"workload {name!r} is not in BENCHMARK.json (known: {known})")

    def config(self, cell: dict) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == cell["config"]:
                cfg = _read_json(
                    os.path.join(self.root, c["file"]), f"configuration {c['name']!r}"
                )
                cfg["name"] = c["name"]
                return cfg
        raise MissingPiece(
            f"workload {cell['name']!r} names configuration {cell['config']!r}, "
            "which BENCHMARK.json does not list"
        )

    def traffic(self, cell: dict) -> dict:
        return _read_json(
            os.path.join(self.home, "traffic", cell["traffic"] + ".json"),
            f"traffic mix {cell['traffic']!r} of workload {cell['name']!r}",
        )

    def _metrics_for(self, group: str, cell: dict) -> list:
        return [
            m for m in self.spec[group]
            if "workloads" not in m or cell["name"] in m["workloads"]
        ]

    def end_to_end(self, cell: dict) -> list:
        return self._metrics_for("end_to_end", cell)

    def per_layer(self, cell: dict) -> list:
        """The cell's per-layer metrics: those that list it or list no cell,
        and whose ``moves`` the cell reports (a metric that moves a tail is
        not read in a cell that is not judged on the tail)."""
        reported = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self._metrics_for("per_layer", cell) if m["moves"] in reported]

    def _load(self, kind: str, name: str, what: str):
        """The module ``<home>/<kind>/<name>.py``, loaded from its file under
        the package's own name, so that its relative imports find the
        modules beside it."""
        path = os.path.join(self.home, kind, name + ".py")
        if not os.path.isfile(path):
            raise MissingPiece(f"{what}: no file {os.path.relpath(path, self.root)}")
        package = self.spec["paths"][0].replace("/", ".")
        spec = importlib.util.spec_from_file_location(
            f"{package}.{kind}.{name.replace('.', '_')}", path
        )
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    def _piece(self, kind: str, name: str, what: str, needs: tuple):
        module = self._load(kind, name, what)
        lacks = [n for n in needs if not callable(getattr(module, n, None))]
        if lacks:
            raise MissingPiece(
                f"{what}: {os.path.relpath(module.__file__, self.root)} defines no "
                + ", ".join(lacks)
            )
        return module

    def reader(self, metric: dict):
        """The ``read(ctx)`` of a per-layer metric's own file."""
        what = f"per-layer metric {metric['name']!r}"
        return self._piece("layer_metrics", metric["name"], what, ("read",)).read

    def reference(self, cfg: dict):
        """The plain reference a configuration names (its ``reference``)."""
        what = f"reference {cfg.get('reference')!r} of configuration {cfg['name']!r}"
        return self._piece(
            "reference", str(cfg.get("reference")), what, ("weight_shapes", "Reference")
        )

    def flops(self, cfg: dict):
        """The operations-from-shapes module a configuration names."""
        what = f"flops module {cfg.get('flops')!r} of configuration {cfg['name']!r}"
        return self._piece("flops", str(cfg.get("flops")), what, ("frame_flops",))
