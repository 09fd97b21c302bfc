"""Shared benchmark-artifact plumbing.

Every writer of ``results/BENCH_run.json`` — the full ``benchmarks/run.py``
sweep and the standalone section benches (serving, scaling) —
goes through this module, so the artifact:

- is written **atomically** (temp file + ``os.replace`` in the same
  directory): a crashed or interrupted bench can never leave a
  half-written JSON for the next diff to choke on;
- carries ``schema_version`` (:data:`BENCH_SCHEMA_VERSION`) and a
  ``generated_utc`` run timestamp, so trajectory tooling can tell stale
  artifacts from current ones and old layouts from new.
"""

from __future__ import annotations

import json
import os
import tempfile
import time

# version 2: adds schema_version + generated_utc envelope (v1 was the bare
# {fast, sections} document written non-atomically)
BENCH_SCHEMA_VERSION = 2

BENCH_RUN_PATH = os.path.join("results", "BENCH_run.json")

__all__ = [
    "BENCH_RUN_PATH",
    "BENCH_SCHEMA_VERSION",
    "atomic_write_json",
    "merge_into_bench_run",
]


def atomic_write_json(path: str, doc: object, *, indent: int = 1) -> None:
    """Write JSON via temp-file + rename so readers never observe a torn
    file.  The temp file lives in the destination directory — ``os.replace``
    must not cross filesystems."""
    d = os.path.dirname(path) or "."
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, prefix=os.path.basename(path) + ".", suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(doc, f, indent=indent, default=str)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def merge_into_bench_run(
    name: str, claims: dict, *, fast: bool, path: str = BENCH_RUN_PATH,
    extra: dict | None = None,
) -> None:
    """Replace (or append) the named section of ``results/BENCH_run.json``
    in place, preserving the others — standalone section benches keep the
    perf trajectory current without clobbering the full sweep's sections.
    Stamps the envelope (schema version + UTC timestamp) on every merge."""
    doc: dict = {"fast": fast, "sections": []}
    if os.path.exists(path):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (json.JSONDecodeError, OSError):
            pass  # corrupt/torn artifact: start a fresh document
    derived = ";".join(f"{k}={v:.2f}" for k, v in claims.items())
    section = {"name": name, "us_per_call": 0.0, "derived": derived, "claims": claims}
    if extra:
        section.update(extra)
    sections = [s for s in doc.get("sections", []) if s.get("name") != name]
    sections.append(section)
    doc["sections"] = sections
    doc["schema_version"] = BENCH_SCHEMA_VERSION
    doc["generated_utc"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    atomic_write_json(path, doc)
