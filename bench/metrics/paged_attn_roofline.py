"""Kernel: device time of the paged-attention kernel's events in the trace
against the least time its work needs on this chip. The work (K and V at
each call's context length in the pool's format, q and output bytes,
4 x heads x head_dim x context FLOPs per query) is counted by
bench/lib/flops.py from the forwards the engine dispatched inside the
traced window; the least time is the larger of FLOPs / peak FLOP/s and
bytes / peak bytes/s (memory binds at these shapes). On a mesh the kernel
time is summed over the chips and the least time is that of all the work
on one chip: chip-seconds against chip-seconds, right for any chip count
(a chip that repeats another's work, as each "model" shard does with the
attention of its "data" shard's rows, lowers the share)."""

from bench.lib import flops, xplane_meta

KERNEL = "%paged_attention"     # HLO name of the Pallas call's op
# its JAX name stack (tf_op): on a mesh XLA names the op after the
# shard_map it was inlined from (`%shard_map.N`), and only this tells it
KERNEL_JIT, PALLAS = "jit(paged_attention)/", "/pallas_call"


def is_kernel(name: str, path: str | None = None) -> bool:
    return name.split(" = ")[0].split(".")[0] == KERNEL or (
        path is not None and KERNEL_JIT in path and path.endswith(PALLAS))


def read(ctx):
    w, m, tr = ctx["window"], ctx["model"], ctx["window"]["trace"]
    if tr is None:
        return None
    paths = (xplane_meta.with_meta(tr) or {}).get("op_scopes", {})
    t = sum(d for n, _, d, _ in tr["device_ops"]
            if is_kernel(n, paths.get(n)))
    fw = [f for f in w["forwards"] if tr["host_t0"] <= f[0] < tr["host_t1"]]
    work = [flops.paged_attention_work(m, size, pos, act)
            for _, size, pos, act in fw]
    fl, nb = sum(x[0] for x in work), sum(x[1] for x in work)
    if t <= 0 or not nb:
        return None
    least = max(fl / ctx["peaks"]["flops_bf16"],
                nb / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / t
