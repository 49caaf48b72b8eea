"""A tiny cell for driving the benchmark on the CPU (not collected)."""
import json
import os

from chipbench import spec

# At this size a sound run's mean gap (``reference.numbers``) read
# 3e-5 to 2.1e-4 over six seeds on the CPU; the faults read far above.
DENSE = {"name": "tiny-dense", "source": "test", "hidden_size": 64,
         "intermediate_size": 128, "num_attention_heads": 4,
         "num_key_value_heads": 2, "num_hidden_layers": 2,
         "vocab_size": 512, "rms_norm_eps": 1e-5, "rope_theta": 10000,
         "tie_word_embeddings": False,
         "engine": {"family": "dense", "qk_norm": False,
                    "dtype": "bfloat16"},
         "limits": {"logit_gap_mean": 0.002}}
# With 8 experts, top-2 and d_model 64, bfloat16 rounding flips a near-tied
# expert choice now and then, which moves that token's logits: over six
# seeds the mean gap read 0 to 0.0042 on the CPU, so this size's limit
# is 0.02.
MOE = dict(DENSE, name="tiny-moe", num_experts=8, num_experts_per_tok=2,
           moe_intermediate_size=32, head_dim=32,
           engine={"family": "moe", "qk_norm": True, "dtype": "bfloat16",
                   "capacity_factor": 4.0},
           limits={"logit_gap_mean": 0.02})
# Wide enough in vocabulary and depth that the int8 control separates
# from the bfloat16 program on the CPU: over six seeds the program's mean
# gap read 0 to 4.3e-4 and the control's 1.3e-3 to 2.7e-3, so its limit
# sits at 8e-4.
CONTROL = dict(DENSE, name="tiny-control", hidden_size=128,
               intermediate_size=256, num_hidden_layers=4,
               vocab_size=4096, limits={"logit_gap_mean": 8e-4})
OPEN = {"kind": "open", "arrivals": {"kind": "poisson", "rate_rps": 12.0},
        "lengths": {"isl": {"choices": [16, 32], "weights": [0.5, 0.5]},
                    "osl": {"lo": 4, "hi": 10}},
        "engine": {"slots": 4, "max_seq": 48}}
# Four clients on four slots, the first sends staggered over 0.4 s.
CLOSED = dict(OPEN, kind="closed", arrivals={
    "kind": "closed", "clients": 4, "stagger_s": 0.4, "cycle": 6})


def cell(config=DENSE, mix=OPEN):
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    e2e, layer = spec.metrics_for("internlm2-1.8b.chat", bench)
    return spec.Cell(name="tiny", chips=1,
                     config=json.loads(json.dumps(config)), traffic=mix,
                     end_to_end=e2e, per_layer=layer)


class Event:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


class Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class Profile:
    def __init__(self, planes):
        self.planes = planes


def hand_built_profile():
    """A 100 ns window: the host steps 0-60 and waits 60-100; the device
    runs two programs, with ops 10-30, 25-40 (overlapping) and 50-55."""
    E, L, P = Event, Line, Plane
    host = P("/host:CPU", [L("python", [
        E("bench.step", 0, 60), E("bench.wait", 60, 40),
        E("unrelated", 0, 100)])])
    dev = P("/device:TPU:0", [
        L("XLA Modules", [E("jit_decode_step(3)", 10, 30),
                          E("jit_prefill(7)", 50, 5)]),
        L("XLA Ops", [E("fusion.1", 10, 20), E("fusion.2", 25, 15),
                      E("copy", 50, 5)])])
    other = P("/device:TPU:1", [L("XLA Ops", [E("x", 0, 100)])])
    return Profile([host, dev, other])


def profile_for_steps(steps):
    """A trace of the given engine steps as a v5e run records it: a
    ``bench.step`` of 40 ms every 50 ms, inside it one prefill program per
    prompt (2 ms each) and then the decode program (30 ms, the same name
    and fingerprint every step), the device's clock 0.2 ms ahead of the
    host's.  The engine's ``engine.step`` span takes the step's first
    39 ms, and a step that decodes reads its tokens back in the last
    one of them (``engine.decode.sample``)."""
    E, L, P = Event, Line, Plane
    ms = 1_000_000
    host, mods = [], []
    for i, s in enumerate(steps):
        t = 50 * ms * i
        host += [E("bench.admit", t - ms, ms // 2), E("bench.step", t, 40 * ms),
                 E("engine.step", t, 39 * ms)]
        if s.decode_lens:
            host.append(E("engine.decode.sample", t + 38 * ms, ms))
        at = t - ms // 5
        for n in s.prefill_lens:
            mods.append(E(f"jit__unknown({n})", at, 2 * ms))
            at += 2 * ms
        if s.decode_lens:
            mods.append(E("jit__unknown(1)", at, 30 * ms))
    ops = [E("fusion", e.start_ns, e.duration_ns) for e in mods]
    return Profile([P("/host:CPU", [L("python", host)]),
                    P("/device:TPU:0", [L("XLA Modules", mods),
                                        L("XLA Ops", ops)])])


def cpu_peaks(devices, chips):
    """Stands in for the look for a chip: CPU tests drive the rest."""
    from chipbench import device
    return device.Peaks("cpu (test)", 1e12, 1e11)
