"""Write the small trace that `test_bench_trace.py` reduces.

    python3 tests/bench/make_probe_trace.py tests/bench/data/v5e_probe.xplane.pb

An XSpace in the layout of a TPU profile: a `/device:TPU:0` plane with an
`XLA Modules` line (one `jit_prefill` and three `jit__decode` module events
per batch) and an `XLA Ops` line (two ops per module, with gaps inside and
between modules), and a `/host:CPU` plane whose Python thread holds two
`bench.batch` spans around `PjitFunction(...)` dispatch events. Times are
whole microseconds, so every expected number can be worked out by hand.
`record_trace.py` records the same sequence from a real chip.
"""

import sys

from jax.profiler import ProfileData

US = 1_000_000  # picoseconds per microsecond
meta, ev_dev_ops, ev_dev_mod, ev_host = {}, [], [], []
def mid(name):
    if name not in meta: meta[name] = len(meta) + 1
    return meta[name]
t = 0
for b in range(2):
    b0 = t; t += 20
    ev_host.append(("PjitFunction(prefill)", t, 30)); ev_dev_mod.append(("jit_prefill(11)", t + 5, 60))
    ev_dev_ops += [("fusion.1", t + 5, 25), ("convolution.2", t + 31, 34)]; t += 70
    for k in range(3):
        ev_host.append(("PjitFunction(_decode)", t, 15)); ev_dev_mod.append(("jit__decode(12)", t + 4, 40))
        ev_dev_ops += [("fusion.7", t + 4, 12), ("dot.3", t + 16, 28)]; t += 46 + 2000
    ev_host.append(("bench.batch", b0, t - b0)); t += 50
def line(i, name, evs):
    es = "".join(f" events {{ metadata_id: {mid(n)} offset_ps: {s*US} duration_ps: {d*US} }}" for n, s, d in evs)
    return f' lines {{ id: {i} name: "{name}" timestamp_ns: 0{es} }}'
def plane(i, name, lines):
    md = "".join(f' event_metadata {{ key: {v} value {{ id: {v} name: "{k}" }} }}' for k, v in meta.items())
    return f'planes {{ id: {i} name: "{name}"{lines}{md} }}'
dev = line(1, "XLA Modules", ev_dev_mod) + line(2, "XLA Ops", ev_dev_ops)
host = line(3, "python3", ev_host)
txt = plane(1, "/device:TPU:0", dev) + "\n" + plane(2, "/host:CPU", host)
open(sys.argv[1], "wb").write(ProfileData.text_proto_to_serialized_xspace(txt))
