"""Integration test for the dry-run driver: one real cell end-to-end in a
subprocess (512 host devices, production 16×16 mesh), asserting the JSON
artifact has coherent roofline terms."""

import json
import os
import subprocess
import sys
import tempfile
import textwrap
from pathlib import Path

# children stay on the CPU: the parent process may hold the chip
CPU_ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}

SCRIPT = textwrap.dedent("""
    import sys, json
    sys.path.insert(0, "src")
    from pathlib import Path
    from repro.launch.dryrun import run_cell   # sets XLA_FLAGS on import

    out = Path(sys.argv[1])
    rec = run_cell("smollm-360m", "decode_32k", "pod", out)
    assert rec["status"] == "ok", rec
    r = rec["roofline"]
    assert rec["chips"] == 256
    assert r["compute_s"] > 0 and r["memory_s"] > 0
    assert r["dominant"] in ("compute", "memory", "collective")
    assert rec["memory"]["argument_bytes"] > 0
    print("DRYRUN_OK", r["dominant"])
""")


def test_dryrun_cell_end_to_end():
    with tempfile.TemporaryDirectory() as td:
        r = subprocess.run([sys.executable, "-c", SCRIPT, td],
                           capture_output=True, text=True, timeout=900,
                           cwd=".", env=CPU_ENV)
        assert "DRYRUN_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]
        cells = list(Path(td).glob("*.json"))
        assert len(cells) == 1
        rec = json.loads(cells[0].read_text())
        assert rec["arch"] == "smollm-360m"


def test_skip_cell_is_recorded():
    with tempfile.TemporaryDirectory() as td:
        script = SCRIPT.replace(
            'run_cell("smollm-360m", "decode_32k", "pod", out)',
            'run_cell("gemma-7b", "long_500k", "pod", out)').replace(
            'assert rec["status"] == "ok", rec',
            'assert rec["status"] == "skipped", rec').replace(
            'r = rec["roofline"]', 'r = None').replace(
            'assert rec["chips"] == 256', 'pass').replace(
            'assert r["compute_s"] > 0 and r["memory_s"] > 0', 'pass').replace(
            'assert r["dominant"] in ("compute", "memory", "collective")',
            'pass').replace(
            'assert rec["memory"]["argument_bytes"] > 0', 'pass').replace(
            'print("DRYRUN_OK", r["dominant"])', 'print("DRYRUN_OK skip")')
        r = subprocess.run([sys.executable, "-c", script, td],
                           capture_output=True, text=True, timeout=300,
                           cwd=".", env=CPU_ENV)
        assert "DRYRUN_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]
