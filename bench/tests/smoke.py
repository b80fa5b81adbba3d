"""A cell of ``BENCHMARK.json`` cut to a size the CPU runs in seconds, for
rehearsing the harness without the chip (Pallas kernels in interpret
mode).  Only the widths and depth of the configuration and the lengths
and rate of the traffic change; everything else is the cell's own."""
import json

import jax

import run

SEED = 2 ** 31 + 12345
SECONDS = 4.0
# at this size the sound program read 0.0-0.0047 over 8 runs and the
# control 0.025-0.072; a token off the greedy pick reads 0.1 or more
LIMIT = 0.015


def smoke_cell(workload: str) -> run.Cell:
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    cell = run.resolve(spec, workload)
    cell.conf = dict(cell.conf, hidden_size=64, num_attention_heads=4,
                     num_key_value_heads=4, intermediate_size=128,
                     vocab_size=512, num_hidden_layers=2,
                     max_position_embeddings=256)
    cell.conf["serving"] = dict(cell.conf["serving"], max_batch=4,
                                max_len=256, chunk=64)
    cell.conf["correct"] = dict(cell.conf["correct"], max_logit_gap=LIMIT)
    cell.traffic = dict(cell.traffic, rate_per_s=2.0, pool=8,
                        input={"mean": 60, "sigma": 0.5, "cap": 160},
                        output={"mean": 8, "sigma": 0.5, "cap": 16},
                        max_total=256, prompt_multiple=16, preroll_s=2,
                        drain_s=5)
    return cell


def steer_to_cpu(monkeypatch) -> None:
    """Run on the CPU: the test, not the harness, skips the look for a
    TPU, takes the TPU's peaks, and keeps the compile cache out."""
    monkeypatch.setattr(run, "require_accelerator",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(run, "peak_table",
                        lambda kind: json.loads(
                            (run.BENCH / "peaks.json").read_text())
                        ["TPU v5 lite"])
    monkeypatch.setattr(run, "use_compile_cache", lambda root: None)
