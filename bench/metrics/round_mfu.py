"""round_mfu: percent of the chip's peak FLOP/s that the whole round uses
while the device runs it.  The traced rounds (traced requests x segment
rounds), each of K clients x batch x local steps samples at the
matrix-multiply FLOPs of one forward and backward pass, over the seconds
in which any operation ran on the device in the traced stretch, over the
chip's published bf16 peak.  Host gaps are left out, so it reads the
device side of the round, which ``rounds_per_s`` mixes with the host
loop."""
from bench import roofline, trace


def read(run):
    t = run.trace
    if t is None or t["window"] is None:
        return None
    busy = trace.busy_seconds(t)
    requests = trace.request_count(t)
    if busy <= 0 or not requests:
        return None
    s, m = run.cell.config["spec"], run.cell.config["model"]
    per_sample = roofline.mlp_train_flops_per_sample(
        [m["in_dim"], m["hidden"], m["hidden"], m["num_classes"]])
    samples = s["num_devices"] * s["batch_size"] * s["local_steps"]
    rounds = requests * run.cell.traffic["segment_rounds"]
    peak = roofline.peaks(run.device_kind)["flops_per_s"]
    return 100.0 * per_sample * samples * rounds / busy / peak
