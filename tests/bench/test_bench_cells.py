"""Cells resolve by name from data files: adding one takes new files and a
``workloads`` entry, and no edit to a file that is there."""
import json
import shutil
from pathlib import Path

import numpy as np

from bench import cells, harness, readers, traffic

ROOT = Path(__file__).resolve().parents[2]


def test_every_cell_of_the_manifest_resolves():
    man = cells.manifest(ROOT)
    for w in man["workloads"]:
        cell = cells.resolve(w["name"], ROOT)
        assert cell.cfg["name"] == w["config"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer, w["name"]
        assert hasattr(cell.reference, "replay")


def test_a_new_cell_needs_only_new_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in tmp_path.rglob("*") if p.is_file()}

    bench = tmp_path / "bench"
    cfg = json.loads((bench / "configs" / "klms_fleet.json").read_text())
    cfg.update(name="klms_tiny", slots=8, tenants=8, num_features=64)
    (bench / "configs" / "klms_tiny.json").write_text(json.dumps(cfg))
    (bench / "configs" / "klms_tiny.py").write_text(
        "from bench.configs.klms_fleet import replay, tenants  # noqa: F401\n"
    )
    # A mix of kinds no cell uses yet: on/off bursts at 3x the mean rate,
    # a hot set that moves, and concept drift, all as data.
    mix = {
        "arrivals": "poisson", "rate": 300,
        "profile": {"period_s": 0.1, "pieces": [[1 / 3, 3.0], [2 / 3, 0.0]]},
        "read_share": 0.5,
        "keys": {"dist": "zipf", "theta": 0.99, "shift": {"every": 40, "by": 3}},
        "drift": {"every": 60},
        "warmup": 40, "size_watermark": 16, "age_watermark": 0.05,
    }
    (bench / "traffic" / "half_reads.json").write_text(json.dumps(mix))
    (bench / "metrics" / "reads_per_flush.py").write_text(
        "def read(obs):\n"
        "    n = sum(1 for s in obs.spans if s.name == 'serve.predict')\n"
        "    return n / len(obs.flushes) if obs.flushes else None\n"
    )
    man = json.loads((tmp_path / "BENCHMARK.json").read_text())
    man["configs"].append({
        "name": "klms_tiny", "source": "https://arxiv.org/abs/1606.03685",
        "file": "bench/configs/klms_tiny.json", "reduced": ["slots", "tenants"],
        "why": "throwaway",
    })
    man["workloads"].append({
        "name": "klms_tiny.half_reads", "config": "klms_tiny",
        "traffic": "half_reads", "chips": 1, "why": "throwaway",
    })
    man["end_to_end"] += [
        {"name": name, "unit": "ms", "better": "lower", "bound": 0.1,
         "source": "host_clock", "workloads": ["klms_tiny.half_reads"]}
        for name in ("write_p95_ms", "read_p95_ms")
    ]
    man["per_layer"].append({
        "name": "reads_per_flush", "unit": "reads", "better": "lower",
        "source": "program_span", "layer": "facade (serve/api.py Server)",
        "moves": "read_p95_ms", "workloads": ["klms_tiny.half_reads"],
    })
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))

    cell = cells.resolve("klms_tiny.half_reads", tmp_path)
    assert cell.cfg["slots"] == 8 and cell.mix["read_share"] == 0.5
    assert cell.reference.replay.__module__.endswith("klms_fleet")
    assert {m["name"] for m in cell.end_to_end} == {
        "setup_s", "write_p95_ms", "read_p95_ms"}
    assert [m["name"] for m, _ in cell.per_layer] == ["reads_per_flush"]
    reader = cell.per_layer[0][1]
    obs = readers.Observation(cfg=cell.cfg, peak={}, flushes=[(3, 5), (1, 1)])
    assert reader.read(obs) == 0.0
    # The harness runs the new cell as it stands, past its look for a chip.
    result, details = harness.run_cell(cell, 2**33 + 7, 0.3, require_tpu=False)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"setup_s", "write_p95_ms", "read_p95_ms"}
    sched = traffic.build(cell.mix, cell.cfg, 1, 0.3)
    due = sched.due[sched.warmup:]
    assert np.all((due % 0.1) <= 0.1 / 3 + 1e-9)  # nothing due in the off phase
    # The files that were there are unchanged, BENCHMARK.json aside.
    for p, data in before.items():
        if p.name != "BENCHMARK.json":
            assert p.read_bytes() == data, p

