"""Device time of the operations or programs matching a pattern in the
traced window, reduced one of three ways:

- ``"share_of_busy_pct"``: that time over the device's busy time, percent;
- ``"ms_per_count"``: that time in ms over the number of operations that
  match ``count_pattern``, divided by ``count_divisor`` (a number, or the
  name of a key of the configuration's ``decoder_config``). With the decode
  kernel's pattern and ``num_layers`` this is device time per decode
  substep: the kernel runs once a layer a substep;
- ``"seconds"``: that time.

``module`` selects whole programs by name (the trace's modules line, or the
``hlo_module`` of each operation); ``op`` selects operations by their stable
name (``trace_reduce.stable_name``)."""


def read(ctx, reduce: str, module: str = None, op: str = None,
         count_pattern: str = None, count_divisor=1):
    tr = ctx["trace"]
    if tr is None or not tr.devices:
        return None
    if op is not None:
        secs, _ = tr.op_time(op, module)
    else:
        secs, _ = tr.module_time(module)
    if reduce == "seconds":
        return secs
    if reduce == "share_of_busy_pct":
        busy = tr.busy_s()
        return 100.0 * secs / busy if busy > 0 else None
    if reduce == "ms_per_count":
        _, n = tr.op_time(count_pattern, module)
        if isinstance(count_divisor, str):
            count_divisor = ctx["config"]["program"]["decoder_config"][
                count_divisor]
        n = n / float(count_divisor)
        return 1000.0 * secs / n if n > 0 else None
    raise ValueError(f"unknown reduction {reduce!r}")
