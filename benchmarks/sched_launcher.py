"""Start ``cronsun_tpu.bin.sched`` ``main()`` unchanged, in the one
process that holds the chip, with a side thread that answers the
benchmark's requests for what only that process can read: the device
as JAX reports it, the device's peak memory, and a profiler trace of a
stretch of the window.

    python benchmarks/sched_launcher.py CONTROL_DIR <bin.sched arguments>

A request is a file ``<name>.req`` (JSON) in CONTROL_DIR; the answer is
``<name>.done`` (JSON), written whole and then renamed.
  {"op": "memory"}                                    -> {"peak_bytes": n}
  {"op": "trace", "dir": d, "start_at": t, "seconds": s}
                                  -> {"started": t0, "stopped": t1}
"""

import json
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def answer(ctl: str, name: str, body: dict):
    tmp = os.path.join(ctl, name + ".tmp")
    with open(tmp, "w") as f:
        json.dump(body, f)
    os.replace(tmp, os.path.join(ctl, name + ".done"))


def serve(ctl: str):
    import jax
    seen = set()
    while True:
        for fn in sorted(os.listdir(ctl)):
            if not fn.endswith(".req") or fn in seen:
                continue
            seen.add(fn)
            name = fn[:-len(".req")]
            try:
                with open(os.path.join(ctl, fn)) as f:
                    req = json.load(f)
                if req["op"] == "memory":
                    peaks = [(d.memory_stats() or {}).get(
                        "peak_bytes_in_use") for d in jax.local_devices()]
                    peaks = [p for p in peaks if p is not None]
                    answer(ctl, name, {"peak_bytes":
                                       max(peaks) if peaks else None})
                elif req["op"] == "trace":
                    time.sleep(max(0.0, req["start_at"] - time.time()))
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    jax.profiler.start_trace(req["dir"],
                                             profiler_options=opts)
                    t0 = time.time()
                    time.sleep(req["seconds"])
                    t1 = time.time()
                    jax.profiler.stop_trace()
                    answer(ctl, name, {"started": t0, "stopped": t1})
                else:
                    answer(ctl, name, {"error": f"unknown op {req['op']}"})
            except Exception as e:  # noqa: BLE001 — the scheduler goes on
                answer(ctl, name, {"error": repr(e)})
        time.sleep(0.1)


def main() -> int:
    ctl, argv = sys.argv[1], sys.argv[2:]
    sys.path.insert(0, REPO)
    import jax
    devs = jax.devices()
    print("benchdevice " + json.dumps({
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}), flush=True)
    threading.Thread(target=serve, args=(ctl,), daemon=True,
                     name="bench-control").start()
    from cronsun_tpu.bin import sched
    return sched.main(argv)


if __name__ == "__main__":
    sys.exit(main())
