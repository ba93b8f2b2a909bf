"""Model flops of the completed requests' rows (padding excluded: a
prefill and ``gen - 1`` decode steps per row, ``bench.counts``) over the
chip-seconds of the window's scheduler steps at the bf16 peak (%).
Step times are the scheduler's own host-clock records."""


def read(run):
    if not run.peak or not run.steps:
        return None
    tr = run.cell.traffic
    rows = sum(r["rows"] for r in run.completed)
    flops = rows * run.counts.served_row_flops(tr["prompt_len"], tr["gen"])
    chip_s = sum(s["t_step"] for s in run.steps) * run.cell.chips
    return 100.0 * flops / (chip_s * run.peak["bf16_flops_per_s"])
