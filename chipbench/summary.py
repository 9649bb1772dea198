"""One compact line per run of ``chipbench/run.py``, or per seed of
``chipbench/readings.py``, from the files given.

    python3 chipbench/summary.py OUT/*.out OUT/readings.jsonl
"""
import json
import sys


def _round(x):
    return round(x, 6) if isinstance(x, float) else x


def run_line(path: str, text: str) -> str:
    lines = [l for l in text.splitlines() if l.startswith("{")]
    if not lines:
        return f"{path}: no result"
    r = json.loads(lines[-1])
    d = r["device"]
    metrics = {k: v["value"] for k, v in r["metrics"].items()}
    checks = {k: _round(v["value"]) for k, v in r["checks"].items()}
    return (f"{path}: correct={r['correct']} attempted={r['attempted']} window_compiles={r.get('window_compiles')} "
            f"metrics={json.dumps(metrics)} peak_gib={d['memory_peak_bytes'] / 2**30:.4f} "
            f"busy_s={d.get('busy_s')} window_s={d.get('window_s')} checks={json.dumps(checks)}")


def readings_lines(path: str, text: str) -> list:
    out = []
    for line in text.splitlines():
        r = json.loads(line)
        for mode in ("program", "fp8", "half_batch", "token"):
            if mode in r:
                shown = {k: _round(v) for k, v in r[mode].items() if k != "worst"}
                out.append(f"{path}: seed={r['seed']} {mode} {json.dumps(shown)} worst={json.dumps(r[mode].get('worst'))}")
    return out


def main(paths) -> int:
    for path in paths:
        text = open(path).read()
        print("\n".join(readings_lines(path, text)) if path.endswith(".jsonl") else run_line(path, text))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
