"""Record the small trace the engine readers' tests read
(``benchmark/tests/data/engine_small.xplane.pb``). Run on the chip, by hand:

    python3 -m benchmark.tests.record_engine_trace chiprun_out/engine_trace [8,40,100]

The chat cell's files at tiny widths (``tests/tiny.py``), deployed through
the program's normal path; inside the harness's window annotation the
engine idles, serves three requests (8, 40 and 100 prompt tokens unless
given: one and two chunks, six tokens each) and idles again. Python tracer off, so that
the file stays small. The ring's records of the window are printed as JSON
(the file's neighbour ``engine_small.turns.json``).

The raw file is ~3 MB, nearly all of it the HLO text and the statistics of
every operation. ``slim`` (run anywhere; it needs TensorFlow's copy of the
``xplane`` protocol) keeps what ``trace_reduce.Trace`` and the readers read
— the first chip's operations and programs with their names cut after the
result's shape, the host's ``rdb.*`` spans with their attributes and the
window annotation — and drops the rest:

    python3 -m benchmark.tests.record_engine_trace slim <raw.xplane.pb> \
        benchmark/tests/data/engine_small.xplane.pb
"""

import json
import sys
import time


def main() -> int:
    import jax
    import jax.profiler as jp

    from benchmark.run import Deployed
    from benchmark.tests.tiny import tiny_cell
    from benchmark.trace_reduce import WINDOW_ANNOTATION

    lens = tuple(int(x) for x in (sys.argv[2:] or ["8,40,100"])[0].split(","))
    cell = tiny_cell("gpt2m-chat-steady")
    dep = Deployed(cell.config, 7, jax.devices()[:1], {})
    try:
        def serve(lens):
            futs = [dep.submit({"tokens": list(range(1, n + 1)),
                                "max_new_tokens": 6})[1] for n in lens]
            return [f.result(timeout=300.0).tokens for f in futs]

        serve(lens)                   # every shape once, outside the trace
        (engine,) = dep.engines
        engine.reset_ttft_window()
        opts = jp.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jp.start_trace(sys.argv[1], profiler_options=opts)
        # A span that began before the session is not in it: let the idle
        # wait under way end before the window opens, and the last one
        # after it closes.
        time.sleep(0.02)
        with jp.TraceAnnotation(WINDOW_ANNOTATION):
            time.sleep(0.02)
            serve(lens)
            time.sleep(0.01)
        time.sleep(0.02)
        jp.stop_trace()
        turns = [t._asdict() for t in engine.turns]
    finally:
        dep.close()
    print(json.dumps({"device_kind": jax.devices()[0].device_kind,
                      "turns": turns}))
    return 0


def slim(src: str, dst: str) -> int:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    from benchmark.trace_reduce import (
        MODULES_LINE,
        OPS_LINE,
        WINDOW_ANNOTATION,
    )

    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    out = xplane_pb2.XSpace()
    for plane in space.planes:
        device = plane.name == "/device:TPU:0"
        if not device and plane.name != "/host:CPU":
            continue
        new = out.planes.add(id=plane.id, name=plane.name)
        used_stats = set()
        for line in plane.lines:
            if device:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                events = list(line.events)
            else:
                events = [
                    e for e in line.events
                    if plane.event_metadata[e.metadata_id].name.startswith(
                        ("rdb.", WINDOW_ANNOTATION))]
                if not events:
                    continue
            nl = new.lines.add(id=line.id, name=line.name,
                               timestamp_ns=line.timestamp_ns)
            for e in events:
                ne = nl.events.add(metadata_id=e.metadata_id,
                                   offset_ps=e.offset_ps,
                                   duration_ps=e.duration_ps)
                if not device:      # the spans' attributes
                    ne.stats.extend(e.stats)
                    for st in e.stats:   # a string's text is metadata too
                        used_stats.add(st.metadata_id)
                        if st.WhichOneof("value") == "ref_value":
                            used_stats.add(st.ref_value)
                md = plane.event_metadata[e.metadata_id]
                name = md.name
                if device and "]" in name:   # stable_name reads up to here
                    name = name[:name.index("]") + 1]
                new.event_metadata[e.metadata_id].id = e.metadata_id
                new.event_metadata[e.metadata_id].name = name
        for sid in used_stats:
            new.stat_metadata[sid].CopyFrom(plane.stat_metadata[sid])
    with open(dst, "wb") as f:
        f.write(out.SerializeToString())
    print(f"{src}: {sum(len(l.events) for p in out.planes for l in p.lines)}"
          f" events kept, {len(out.SerializeToString())} bytes")
    return 0


if __name__ == "__main__":
    if sys.argv[1:2] == ["slim"]:
        raise SystemExit(slim(sys.argv[2], sys.argv[3]))
    raise SystemExit(main())
