"""Writes scoped.xplane.pb: a trace in the layout of a TPU v5e profile
whose device ops carry their `op_name` (the `tf_op` stat), for
bench/tests/test_bench_scopes.py. One device plane: "XLA Modules" holds
three steps, each an admission program then a tick program; "XLA Ops"
holds their ops, each naming its instruction's op_name on its event
metadata as a TPU v5e trace does (with the trailing ":" of an empty op
type), save the while loop and a copy, which carry none there either.
One host plane: the harness's `bench.*` spans and the DiT scheduler's
`dit.sched.*` spans of each step.

    python bench/tests/data/make_scoped_xplane.py
"""
import pathlib

from jax.profiler import ProfileData

PS_PER_US = 1_000_000
TF_OP = 1  # stat metadata id of "tf_op"
# event metadata id: (HLO text, tf_op stat on the metadata or "")
DEVICE = {
    1: ("jit_admit_fresh(7)", ""),
    2: ("jit_tick(9)", ""),
    3: ("%fusion.20 = f32[8,8] fusion(...)",
        "jit(admit_fresh)/while/body/sla.plan/score/reduce_sum:"),
    4: ("%while.3 = (s32[]) while(...)", ""),
    5: ("%fusion.7 = f32[256,256] fusion(...)",
        "jit(tick)/while/body/closed_call/dit.mlp/dot_general:"),
    6: ("%sla_fwd.2 = (f32[8,256,128]) custom-call(...)",
        "jit(tick)/while/body/closed_call/jit(sla_fwd)/sla.sparse/sla_fwd/"
        "pallas_call:"),
    7: ("%sort.3 = s32[8,16,16] sort(...)",
        "jit(tick)/while/body/closed_call/sla.plan/classify/sort:"),
    8: ("%copy.1 = f32[256] copy(...)", ""),
    9: ("%fusion.9 = f32[256] fusion(...)",
        "jit(tick)/dit.commit/select_n:"),
}
HOST = {"bench.window": 1, "bench.step": 2, "dit.sched.step": 3,
        "dit.sched.admit": 4, "dit.sched.tick": 5, "dit.sched.readback": 6,
        "dit.sched.retire": 7}


def _event(m, o, d):
    return (f"    events {{ metadata_id: {m} offset_ps: {o * PS_PER_US} "
            f"duration_ps: {d * PS_PER_US} }}\n")


def _line(i, name, events):
    return (f"  lines {{\n    id: {i}\n    name: \"{name}\"\n"
            f"    timestamp_ns: 0\n{''.join(events)}  }}\n")


def _device_meta():
    out = []
    for i, (name, op_name) in DEVICE.items():
        name = name.replace('"', '\\"')
        stat = (f" stats {{ metadata_id: {TF_OP} str_value: \"{op_name}\" }}"
                if op_name else "")
        out.append(f"  event_metadata {{ key: {i} value {{ id: {i} "
                   f"name: \"{name}\"{stat} }} }}\n")
    out.append(f"  stat_metadata {{ key: {TF_OP} value {{ id: {TF_OP} "
               f"name: \"tf_op\" }} }}\n")
    return "".join(out)


def _host_meta():
    return "".join(f"  event_metadata {{ key: {v} value {{ id: {v} "
                   f"name: \"{k}\" }} }}\n" for k, v in HOST.items())


def xspace() -> bytes:
    """Window 0-3200 us; step i starts at s = 100 + 1000 i (us):

    host    bench.step [s, s+950], dit.sched.step [s+10, s+900],
            admit [s+10, s+200], tick [s+200, s+230],
            readback [s+230, s+800], retire [s+800, s+820]
    device  admission [s+20, s+80] (fusion.20: sla.plan/score);
            tick [s+240, s+790]: while [s+240, s+700] around fusion.7
            (dit.mlp, 150), sla_fwd.2 (sla.sparse, 200), sort.3
            (sla.plan/classify, 50); copy.1 [s+650, s+700] (no
            op_name); fusion.9 [s+760, s+790] (dit.commit)

    so the idle gaps lie in the admission (s+80 to s+240, around
    s+160), the read-back (s+700 to s+760) and between steps."""
    modules, ops, host = [], [], [_event(1, 0, 3200)]
    for i in range(3):
        s = 100 + i * 1000
        host += [_event(2, s, 950), _event(3, s + 10, 890),
                 _event(4, s + 10, 190), _event(5, s + 200, 30),
                 _event(6, s + 230, 570), _event(7, s + 800, 20)]
        modules += [_event(1, s + 20, 60), _event(2, s + 240, 550)]
        ops += [_event(3, s + 20, 60),
                _event(4, s + 240, 460), _event(5, s + 250, 150),
                _event(6, s + 400, 200), _event(7, s + 600, 50),
                _event(8, s + 650, 50), _event(9, s + 760, 30)]
    text = ("planes {\n  id: 1\n  name: \"/device:TPU:0\"\n"
            + _line(1, "XLA Modules", modules) + _line(2, "XLA Ops", ops)
            + _device_meta() + "}\n"
            + "planes {\n  id: 2\n  name: \"/host:CPU\"\n"
            + _line(1, "python", host) + _host_meta() + "}\n")
    return ProfileData.text_proto_to_serialized_xspace(text)


if __name__ == "__main__":
    out = pathlib.Path(__file__).resolve().parent / "scoped.xplane.pb"
    out.write_bytes(xspace())
