"""Tiny versions of the benchmark's configurations and traffic mixes for
CPU tests: the same harness, engine settings and traffic kinds at toy
widths and lengths."""
import json

import _paths  # noqa: F401
from bench import cells, traffic

TINY_MODEL = dict(hidden_size=64, intermediate_size=128,
                  num_attention_heads=4, num_key_value_heads=2,
                  num_hidden_layers=2, vocab_size=256)
# wide enough heads (128, as both configurations' padded lanes) and vocab
# that the float8 control's error reaches the configurations' limit
CONTROL_MODEL = dict(hidden_size=512, intermediate_size=1376,
                     num_attention_heads=4, num_key_value_heads=2,
                     num_hidden_layers=2, vocab_size=4096, head_dim=128)
# replies long enough that the check compares some hundreds of positions,
# where the control's widest gap stands clear of the limit
CONTROL_LENGTHS = {
    "shortdoc": dict(output_len={"dist": "uniform", "min": 24, "max": 40}),
    "sessions": dict(reply_len={"dist": "uniform", "min": 40, "max": 64},
                     greedy_every=1)}
TINY_ENGINE = dict(max_slots=4, max_seq=256, min_bucket=16,
                   prefill_tokens_per_step=32)
TINY_TRAFFIC = {
    "offline": dict(prompt_len={"dist": "uniform", "min": 40, "max": 90},
                    output_len={"dist": "uniform", "min": 4, "max": 12},
                    backlog_per_slot=2),
    "sessions": dict(rate_per_s=4.0,
                     system_prompts={"count": 4, "len": 32, "zipf_s": 1.0},
                     user_len={"dist": "lognormal", "median": 12,
                               "sigma": 0.8, "min": 4, "max": 48},
                     reply_len={"dist": "lognormal", "median": 8,
                                "sigma": 0.5, "min": 4, "max": 16},
                     think_s={"dist": "exponential", "mean": 0.3},
                     max_context=200, greedy_every=2),
}
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
# the metric entries an open-loop cell takes (PERF.md, section 7 (b)),
# for as long as BENCHMARK.json holds no such cell
OPEN_METRICS = {
    "end_to_end": [
        {"name": "ttft_p95_ms", "unit": "ms", "source": "host_clock"},
        {"name": "itl_p95_ms", "unit": "ms", "source": "host_clock"}],
    "per_layer": [
        {"name": "queue_wait_p95_ms", "unit": "ms", "source": "program_counter"},
        {"name": "prefix_hit_frac", "unit": "fraction",
         "source": "program_counter"},
        {"name": "decode_call_ms.open", "unit": "ms", "source": "program_span"},
        {"name": "device_idle_frac.open", "unit": "fraction",
         "source": "device_trace"}]}
# (configuration, traffic mix) pairs the tests shrink
OFFLINE = ("yi-6b", "shortdoc")
OPEN = ("smollm-135m", "sessions")


def tiny(config: str, mix: str, chips: int = 1,
         model: dict = TINY_MODEL, lengths: dict = None) -> cells.Cell:
    """A cell of ``config`` under ``mix``, shrunk to ``model`` (and
    ``lengths``, traffic keys over the tiny ones); its metrics are those
    BENCHMARK.json gives a cell of the same traffic kind (for an
    open-loop mix with no such cell, ``OPEN_METRICS``)."""
    bench = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
    conf = json.loads((cells.ROOT / "bench" / "configs"
                       / f"{config}.json").read_text())
    conf.update(model)
    conf["engine"] = dict(conf["engine"], **TINY_ENGINE)
    spec = traffic.load(cells.TRAFFIC_DIR / f"{mix}.json")
    kind = spec["kind"]
    spec.update(TINY_TRAFFIC[kind], **(lengths or {}))
    if "shared_prefix" in spec:      # one tiny block of template
        spec["shared_prefix"] = {"len": 32}
    like = [w["name"] for w in bench["workloads"]
            if traffic.load(cells.TRAFFIC_DIR / f"{w['traffic']}.json")
            ["kind"] == kind]
    applies = (lambda m: "workloads" not in m
               or any(c in m["workloads"] for c in like))
    e2e = [m for m in bench["end_to_end"] if applies(m)]
    per_layer = [m for m in bench["per_layer"] if applies(m)]
    if kind == "sessions" and not like:
        e2e += OPEN_METRICS["end_to_end"]
        per_layer += OPEN_METRICS["per_layer"]
    return cells.Cell(f"tiny.{config}.{mix}", chips, config, conf,
                      cells.model_dims(conf), mix, spec, e2e, per_layer)
