"""The step's share of its roofline: the least time the chip could take for
the configuration's own work count (``benchmark/work_counts/``: the larger
of operations over peak and bytes over HBM bandwidth) over the measured
device time per batch. Prints which of the two bounds it."""


def read(art):
    p, work, peaks = art.get("profile"), art.get("work"), art.get("peaks")
    if not p or not p.get("batches") or not work or not peaks:
        return None
    t_ops = work["flops"] / peaks[work["peak"]]
    t_mem = work["bytes"] / peaks["hbm_bytes_per_s"]
    print(f"[bench] step_roofline: floor {max(t_ops, t_mem) * 1e3:.3f} ms "
          f"({'operations' if t_ops >= t_mem else 'bytes'} bind: "
          f"{t_ops * 1e3:.3f} ms of {work['peak']}, {t_mem * 1e3:.3f} ms of HBM)")
    return 100.0 * max(t_ops, t_mem) / (p["busy_s"] / p["batches"])
