"""A state-space mixer's state update in the decode programs, from the device
trace and the engines' turn ring: ``metric`` is

- ``"roofline_pct"``: the update's share of its memory roofline, percent.
  The bytes the traced window's decode substeps HAD to move for the state
  (``benchmark/ssm_counts.py``: the MODEL's work from the configuration
  file's published keys, ``heads x head x state x 4 B x 2`` a slot a layer a
  substep, whatever implements the update) over the chip's peak bytes/s, over
  the device time of the operations that read or write the plane;
- ``"dev_share_pct"``: that time over the device's busy time, percent.

The substeps: every layer of every decode substep calls the paged decode
kernel once, so the kernel's calls inside the decode programs (``count``
inside ``module``) are the (substep, layer) pairs the trace holds. The slots
that advanced: the substep-weighted mean of ``Turn.active`` over the ring's
scans dispatched inside the traced window. The time: the operations inside
the decode programs whose result is the plane or one layer of it, by SHAPE,
``f32[(layers,) slots, heads, head, state]`` (the update, written in
place), or the state's READ-OUT ``y = S C``, ``f32[slots, heads, head]``
(XLA writes it as a fusion of its own that reads the state a second time;
the TPU's trace carries no scope; ``read_in`` in the metric's file lists
the names read on the chip); a Pallas kernel would be taken by its name,
given as ``kernel``.

``None``, never 0, without a trace, for a configuration file without
``mamba_d_state``, where no operation matches, or where the ring holds no
scan of the window."""

from __future__ import annotations

import re

from benchmark.ssm_counts import scan_bytes


def plane_pattern(config: dict, kernel: str = "") -> str:
    """The stable names (``trace_reduce.stable_name``) of operations whose
    first result is the state plane ``[layers, slots, heads, head, state]``
    in float32 or one layer of it, or the state's read-out ``[slots,
    heads, head]`` in float32."""
    slots = int(config["deployment"]["llm"]["num_slots"])
    heads, head, state = (int(config[k]) for k in (
        "mamba_n_heads", "mamba_d_head", "mamba_d_state"))
    layers = int(config["num_hidden_layers"])
    plane = (rf"_f32_(?:{layers}_)?{slots}_{heads}_{head}_{state}_$"
             rf"|_f32_{slots}_{heads}_{head}_$")
    return f"{plane}|{kernel}" if kernel else plane


def read(ctx, metric: str, module: str, count: str, kernel: str = ""):
    if metric not in ("roofline_pct", "dev_share_pct"):
        raise ValueError(f"unknown metric {metric!r}")
    tr, win = ctx["trace"], ctx["trace_host_window"]
    cfg = ctx["config"]
    if tr is None or win is None or not tr.devices:
        return None
    if "mamba_d_state" not in cfg:
        return None
    secs, ops = tr.op_time(plane_pattern(cfg, kernel), module)
    busy = tr.busy_s()
    if not ops or secs <= 0 or busy <= 0:
        return None
    if metric == "dev_share_pct":
        return 100.0 * secs / busy
    _, calls = tr.op_time(count, module)
    layers = int(cfg["num_hidden_layers"])
    lo = ctx["run"]["t0"] * 1000.0
    work = steps = 0.0
    for eng in ctx["engines"]:
        ring = getattr(eng, "turns", None)
        if ring is None:
            return None
        for t in list(ring.copy()):   # one call: the engine may still append
            if (t.kind == "turn" and lo + win[0] * 1000.0 <= t.t_dispatch
                    < lo + win[1] * 1000.0):
                work += t.active * t.substeps
                steps += t.substeps
    if not calls or not steps:
        return None
    active, substeps = work / steps, calls / layers
    total = scan_bytes(cfg, active, substeps)
    least_s = total / ctx["peaks"]["hbm_bytes_per_s"]
    print(f"ssm: state update: {ops:.0f} operations, {secs * 1000.0:.1f} ms "
          f"on the device in the trace for {substeps:.0f} substeps of "
          f"{active:.1f} advancing slots over {layers} layers; they had to "
          f"move {total / 1e9:.3f} GB ({least_s * 1000.0:.1f} ms at the "
          "peak)", flush=True)
    return 100.0 * least_s / secs
