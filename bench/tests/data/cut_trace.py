"""Cut a chip trace to its traced window's first ``ms`` milliseconds.

    python3 cut_trace.py <trace.xplane.pb> <out.xplane.pb> <ms>

Keeps the benchmark's host spans and the TPU plane's ``XLA Ops`` and
``XLA Modules`` events that start inside the cut, each clipped to it, and
ends ``bench_window`` at the cut.  ``sedov_t2_s3_cut.xplane.pb`` is the
first 25 ms of a 3-step trace of ``sedov_t2.s3`` recorded on a TPU v5
lite by ``bench/calibrate.py --save-trace``.
"""
import sys
from jax.profiler import ProfileData

src, dest, ms = sys.argv[1], sys.argv[2], float(sys.argv[3])
p = ProfileData.from_file(src)
SPANS = ('bench_window', 'courant_dt', 'rk3_step', 'dt_sync')
host = [(e.name, e.start_ns, e.end_ns) for pl in p.planes if pl.name == '/host:CPU'
        for ln in pl.lines for e in ln.events if e.name in SPANS]
w0 = [s for n, s, e in host if n == 'bench_window'][0]
w1 = w0 + ms * 1e6
def clip(evs):
    return [(n, s, min(e, w1)) for n, s, e in evs if s < w1]
host = [(n, s, w1 if n == 'bench_window' else e) for n, s, e in clip(host)]
dev = {}
for pl in p.planes:
    if pl.name == '/device:TPU:0':
        for ln in pl.lines:
            if ln.name in ('XLA Ops', 'XLA Modules'):
                dev[ln.name] = clip([(e.name, e.start_ns, e.end_ns) for e in ln.events])

def plane(pid, name, lines):
    names = sorted({n for _, evs in lines for n, _, _ in evs})
    idx = {n: i + 1 for i, n in enumerate(names)}
    out = f'planes {{ id: {pid} name: "{name}"\n'
    for lid, (lname, evs) in enumerate(lines, 1):
        out += f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0\n'
        for n, s, e in evs:
            out += f'events {{ metadata_id: {idx[n]} offset_ps: {int(s) * 1000} duration_ps: {int(e - s) * 1000} }}\n'
        out += '}\n'
    for n, i in idx.items():
        esc = n.replace('\\', '\\\\').replace('"', '\\"')
        out += f'event_metadata {{ key: {i} value {{ id: {i} name: "{esc}" }} }}\n'
    return out + '}\n'

txt = plane(1, '/host:CPU', [('python3', host)]) + plane(2, '/device:TPU:0', [(k, v) for k, v in dev.items()])
open(dest, 'wb').write(ProfileData.text_proto_to_serialized_xspace(txt))
print(len(dev['XLA Ops']), 'ops')
