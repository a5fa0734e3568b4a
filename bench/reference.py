"""Plain reference of the fluid network simulation the benchmark checks.

A straightforward `jax.numpy` restatement of the model the program
simulates, written from its description (paper section 3, and the fluid
model: flows inject at their congestion window's rate, bytes advance one
link per tick through FIFO-fair queues with gentle RED drops, acks and loss
signals return one RTT later, and each job runs its phase program of
compute and communication).  It imports nothing of the program: the fabric,
the jobs and every constant come from the configuration file.

Its own layout differs from the program's on purpose: bytes in the fabric
are held per flow and hop (``[N, H]``), and link totals are segment sums
over the hop table, where the program keeps dense ``[links, flows]`` arrays.

The loss draws use the same seeded stream as the program (threefry keys
split five ways per tick, each lane's uniform a keyed hash of its index),
so that at small sizes on the CPU the two agree tick for tick until the
first rounding difference.  On the chip they part after a few hundred
ticks, as any two float32 programs of this model do, and are compared by
their statistics.

``dtype`` is the precision the whole simulation runs in: float32 as the
configuration states, or bfloat16 for the control that the comparison must
reject.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from plan import job_paths, shuffled_programs, sim_time

MAX_ITERS = 128
N_CHUNKS = 400


def fabric(config: dict) -> dict:
    """Static arrays of the deployment: links, per-flow hop table, jobs."""
    caps, paths = job_paths(config)
    spj = int(config["transport"]["sockets_per_job"])
    h = max(len(p) for p in paths)
    hops, f2j = [], []
    for j, path in enumerate(paths):
        for _ in range(spj):
            hops.append(list(path) + [-1] * (h - len(path)))
            f2j.append(j)
    hops = np.asarray(hops, np.int32)
    return {"caps": np.asarray(caps, np.float64), "hops": hops,
            "n_hops": (hops >= 0).sum(axis=1).astype(np.int32),
            "f2j": np.asarray(f2j, np.int32), "spj": spj,
            "n_jobs": len(paths)}


def programs(config: dict, orders) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """[K, J, P] compute seconds and comm bytes (scaled), and [J] phases."""
    scale = float(config["work_scale"])
    per_point = [shuffled_programs(config, o) for o in orders]
    p_max = max(len(v["compute_s"]) for v in config["programs"].values())
    k, j = len(per_point), len(per_point[0])
    comp = np.zeros((k, j, p_max))
    comm = np.zeros((k, j, p_max))
    for a, names in enumerate(per_point):
        for b, name in enumerate(names):
            prog = config["programs"][name]
            n = len(prog["compute_s"])
            comp[a, b, :n] = np.asarray(prog["compute_s"]) * scale
            comm[a, b, :n] = np.asarray(prog["comm_bytes"]) * scale
    phases = np.asarray([len(config["programs"][n]["compute_s"])
                         for n in per_point[0]], np.int32)
    return comp, comm, phases


def _mix32(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _uniform(key, n: int, dtype):
    lanes = jnp.arange(n, dtype=jnp.uint32)
    h = _mix32(_mix32(lanes ^ key[0].astype(jnp.uint32))
               ^ key[1].astype(jnp.uint32))
    return ((h >> jnp.uint32(8)).astype(jnp.float32)
            * jnp.float32(1 / (1 << 24))).astype(dtype)


def _simulate(consts: dict, n_ticks: int, dtype, comp, comm, wi, seed):
    """One sweep point; returns the chunk traces and iteration record."""
    c = {k: (jnp.asarray(v, dtype) if isinstance(v, float) else v)
         for k, v in consts.items()}
    hops, n_hops, f2j = consts["hops"], consts["n_hops"], consts["f2j"]
    n_flows, h_max = hops.shape
    n_jobs, m = consts["n_jobs"], consts["caps"].shape[0]
    d = consts["rtt_ticks"]
    caps = jnp.asarray(consts["caps"], dtype)
    link_of = jnp.where(hops >= 0, hops, m)            # [N, H]; m = none
    live = hops >= 0
    last = jnp.arange(h_max)[None, :] == (n_hops - 1)[:, None]
    spj_inv = jnp.asarray(1.0 / consts["spj"], dtype)
    comp = comp.astype(dtype)
    comm = comm.astype(dtype)
    total = comm.sum(axis=1)                           # [J] bytes/iteration
    dt, mss = c["dt"], c["mss"]
    jobs = jnp.arange(n_jobs)
    iters = jnp.arange(MAX_ITERS)
    tpc = max(1, n_ticks // N_CHUNKS)
    n_chunks = n_ticks // tpc

    # sums over links and jobs as products with 0/1 incidence matrices, at
    # full float32 precision (a TPU multiplies float32 in bfloat16 passes
    # unless told otherwise)
    on_link = (link_of.reshape(-1)[:, None] == jnp.arange(m)[None, :]
               ).astype(dtype)                         # [N*H, M]
    in_job = (f2j[:, None] == jnp.arange(n_jobs)[None, :]).astype(dtype)
    exact = jax.lax.Precision.HIGHEST

    def link_sum(x):                                   # [N, H] -> [M]
        return jnp.dot(x.reshape(-1), on_link, precision=exact)

    def job_sum(x):                                    # [N] -> [J]
        return jnp.dot(x, in_job, precision=exact)

    zn = jnp.zeros((n_flows,), dtype)
    zj = jnp.zeros((n_jobs,), dtype)
    st = dict(
        cwnd=jnp.full((n_flows,), c["init_cwnd"], dtype),
        ssthresh=jnp.full((n_flows,), c["init_ssthresh"], dtype),
        cooldown=zn, bsent=zn, ratio=zn, prev_ack=zn,
        iter_gap=jnp.full((n_flows,), c["init_gap"], dtype),
        max_gap=jnp.full((n_flows,), c["init_gap"], dtype),
        q=jnp.zeros((n_flows, h_max), dtype),
        tr=jnp.zeros((n_flows, h_max), dtype),
        ring_del=jnp.zeros((d, n_flows), dtype),
        ring_loss=jnp.zeros((d, n_flows), bool),
        ptr=jnp.asarray(0, jnp.int32),
        to_send=zn, to_deliver=zn,
        phase=jnp.zeros((n_jobs,), jnp.int32),
        in_comm=jnp.zeros((n_jobs,), bool),
        t_rem=comp[:, 0], iter_idx=jnp.zeros((n_jobs,), jnp.int32),
        iter_start=zj,
        iter_times=jnp.full((n_jobs, MAX_ITERS), jnp.nan, dtype),
        key=jax.random.PRNGKey(seed), tick=jnp.asarray(0, jnp.int32),
        acc_util=jnp.zeros((m,), dtype), acc_jobbytes=zj)

    def tick(st, _):
        t = st["tick"].astype(dtype) * dt
        key, k_loss, _, _, _ = jax.random.split(st["key"], 5)

        # job phase machine: a job in compute counts down, then enters its
        # next communication sub-phase; each socket takes an equal share
        in_comm = st["in_comm"]
        t_rem = jnp.where(in_comm, st["t_rem"], st["t_rem"] - dt)
        enter = ~in_comm & (t_rem <= 0)
        in_comm = in_comm | enter
        quota = comm[jobs, st["phase"]][f2j] * spj_inv
        enter_f = enter[f2j]
        to_send = jnp.where(enter_f, quota, st["to_send"])
        to_deliver = jnp.where(enter_f, quota, st["to_deliver"])

        # injection at the window's rate
        rate = st["cwnd"] * c["byte_rate"]
        sending = in_comm[f2j] & (to_send > 0)
        inj = jnp.where(sending, jnp.minimum(rate * dt, to_send), 0)
        to_send = to_send - inj

        # links: RED at enqueue on the queue seen before it, then each link
        # serves cap*dt split in proportion to what every flow has queued
        incoming = st["tr"].at[:, 0].add(inj)
        q_len = link_sum(st["q"])
        p = (jnp.clip((q_len - c["qmin"]) / (c["qmax"] - c["qmin"]), 0, 1)
             * c["pmax"]
             + jnp.clip((q_len - c["qmax"]) / c["qmax"], 0, 1) * (1 - c["pmax"]))
        full = (q_len >= c["buffer"]).astype(dtype)
        drop = jnp.concatenate([jnp.minimum(p + full, 1),
                                jnp.zeros((1,), dtype)])[link_of]
        dropped = jnp.where(live, incoming * drop, 0)
        q = st["q"] + jnp.where(live, incoming - dropped, 0)
        tot = link_sum(q)
        serve = jnp.where(tot > 0, jnp.minimum(1, caps * dt
                                                / jnp.maximum(tot, 1e-9)), 0)
        dep = q * jnp.concatenate([serve, jnp.zeros((1,), dtype)])[link_of]
        q = q - dep
        delivered = jnp.where(last, dep, 0).sum(axis=1)
        fwd = jnp.where(last, 0, dep)
        tr = jnp.concatenate([jnp.zeros((n_flows, 1), dtype), fwd[:, :-1]],
                             axis=1)

        dropped_f = dropped.sum(axis=1)
        loss = _uniform(k_loss, n_flows, dtype) < -jnp.expm1(-dropped_f / mss)
        to_send = to_send + dropped_f

        # feedback returns one RTT later
        ptr = st["ptr"]
        fb_del, fb_loss = st["ring_del"][ptr], st["ring_loss"][ptr]
        ring_del = st["ring_del"].at[ptr].set(delivered)
        ring_loss = st["ring_loss"].at[ptr].set(loss)

        # a sub-phase ends when every socket of the job has delivered its
        # share (to half a packet), or has nothing left anywhere to deliver
        to_deliver = jnp.maximum(to_deliver - delivered, 0)
        in_net = q.sum(axis=1) + tr.sum(axis=1)
        done = (to_deliver <= 0.5 * mss) | ((to_send <= 0)
                                             & (in_net <= 0.5 * mss))
        comm_done = in_comm & (job_sum((~done).astype(dtype)) == 0)
        last_phase = st["phase"] >= consts["n_phases"] - 1
        iter_done = comm_done & last_phase
        phase = jnp.where(comm_done, jnp.where(last_phase, 0, st["phase"] + 1),
                          st["phase"])
        in_comm = in_comm & ~comm_done
        slot = jnp.minimum(st["iter_idx"], MAX_ITERS - 1)
        write = iter_done[:, None] & (slot[:, None] == iters[None, :])
        iter_times = jnp.where(write, (t - st["iter_start"])[:, None],
                               st["iter_times"])
        iter_idx = st["iter_idx"] + iter_done.astype(jnp.int32)
        iter_start = jnp.where(iter_done, t, st["iter_start"])
        t_rem = jnp.where(comm_done, comp[jobs, phase], t_rem)

        # Algorithm 1 on the delayed acks, bytes counted per job
        acks = fb_del / mss
        has_ack = acks > 0
        bsent_new = st["bsent"] + acks * mss
        job_bytes = job_sum(bsent_new)[f2j]
        gap = t - st["prev_ack"]
        max_gap_new = jnp.maximum(st["max_gap"], gap)
        boundary = has_ack & (gap > c["g"] * st["iter_gap"])
        gap_ewma = (1 - c["gamma"]) * st["iter_gap"] + c["gamma"] * max_gap_new
        ratio_new = jnp.minimum(1, job_bytes * (1 / jnp.maximum(total[f2j], 1)))
        bsent = jnp.where(boundary, 0, jnp.where(has_ack, bsent_new,
                                                  st["bsent"]))
        ratio = jnp.where(boundary, 0, jnp.where(has_ack, ratio_new,
                                                  st["ratio"]))
        prev_ack = jnp.where(has_ack, t, st["prev_ack"])
        iter_gap = jnp.where(boundary, gap_ewma, st["iter_gap"])
        max_gap = jnp.where(boundary, c["init_gap"],
                            jnp.where(has_ack, max_gap_new, st["max_gap"]))

        # Reno, with MLTCP-WI scaling the additive increase by F(ratio)
        f = jnp.where(wi, c["slope"] * ratio + c["intercept"],
                      jnp.asarray(1, dtype))
        cwnd = st["cwnd"]
        grow = jnp.where(cwnd < st["ssthresh"], acks,
                         f * acks / jnp.maximum(cwnd, 1e-6))
        cut_now = fb_loss & (st["cooldown"] <= 0)
        cut = jnp.maximum(c["beta"] * cwnd, c["min_cwnd"])
        new = dict(
            cwnd=jnp.where(cut_now, cut, cwnd + grow),
            ssthresh=jnp.where(cut_now, jnp.maximum(cut, 2), st["ssthresh"]),
            cooldown=jnp.where(cut_now, c["rtt"],
                               jnp.maximum(st["cooldown"] - dt, 0)),
            bsent=bsent, ratio=ratio, prev_ack=prev_ack, iter_gap=iter_gap,
            max_gap=max_gap, q=q, tr=tr, ring_del=ring_del,
            ring_loss=ring_loss, ptr=(ptr + 1) % d, to_send=to_send,
            to_deliver=to_deliver, phase=phase, in_comm=in_comm, t_rem=t_rem,
            iter_idx=iter_idx, iter_start=iter_start, iter_times=iter_times,
            key=key, tick=st["tick"] + 1,
            acc_util=st["acc_util"] + link_sum(dep) / (caps * dt),
            acc_jobbytes=st["acc_jobbytes"] + job_sum(delivered))
        return new, None

    def chunk(st, _):
        st = dict(st, acc_util=jnp.zeros((m,), dtype), acc_jobbytes=zj)
        st, _ = jax.lax.scan(tick, st, None, length=tpc)
        out = dict(util=st["acc_util"] / tpc,
                   t=st["tick"].astype(dtype) * dt,
                   jobtput=st["acc_jobbytes"] / (tpc * dt))
        return st, out

    st, trace = jax.lax.scan(chunk, st, None, length=n_chunks)
    return dict(trace, iter_times=st["iter_times"], iter_counts=st["iter_idx"])


def constants(config: dict) -> dict:
    fab = fabric(config)
    tr, ml, red = config["transport"], config["mltcp"], config["red"]
    dt = float(config["dt_s"])
    return dict(
        fab, dt=dt, mss=float(tr["mss_bytes"]), rtt=float(tr["rtt_s"]),
        byte_rate=float(tr["mss_bytes"]) / float(tr["rtt_s"]),
        rtt_ticks=max(1, int(round(float(tr["rtt_s"]) / dt))),
        init_cwnd=float(tr["init_cwnd"]), init_ssthresh=1e9,
        min_cwnd=float(tr["min_cwnd"]), beta=float(tr["reno_beta"]),
        slope=float(ml["WI"]["slope"]), intercept=float(ml["WI"]["intercept"]),
        g=float(ml["g"]), gamma=float(ml["gamma"]),
        init_gap=float(ml["init_comm_gap_s"]),
        qmin=float(red["qmin_bytes"]), qmax=float(red["qmax_bytes"]),
        pmax=float(red["pmax"]), buffer=float(red["buffer_bytes"]))


@partial(jax.jit, static_argnums=(0, 1, 2))
def _run(consts_key, n_ticks, dtype, comp, comm, wi, seed):
    consts = dict(consts_key)
    consts = {k: (np.asarray(v) if isinstance(v, tuple) else v)
              for k, v in consts.items()}
    return jax.vmap(partial(_simulate, consts, n_ticks, dtype))(
        comp, comm, wi, seed)


def _hashable(consts: dict) -> tuple:
    out = []
    for k, v in sorted(consts.items()):
        if isinstance(v, np.ndarray):
            v = tuple(v.tolist()) if v.ndim == 1 else tuple(map(tuple, v.tolist()))
        out.append((k, v))
    return tuple(out)


def simulate(config: dict, points, *, dtype=jnp.float32,
             seconds: float | None = None) -> list[dict]:
    """Simulate ``points`` — (variant, seed, order) triples — and return one
    dict of numpy outputs per point: ``iter_times`` per job (completed
    iterations only), ``trace_util`` [C, M], ``trace_jobtput`` [C, J] and
    ``trace_t`` [C], as the program's `SimResult` names them."""
    if config["transport"]["algo"] != "reno":
        raise NotImplementedError("the reference models Reno only")
    consts = constants(config)
    comp, comm, phases = programs(config, [o for _, _, o in points])
    consts["n_phases"] = phases
    n = int(round((sim_time(config) if seconds is None else seconds)
                  / consts["dt"]))
    wi = np.asarray([v == "WI" for v, _, _ in points])
    seeds = np.asarray([s for _, s, _ in points], np.int32)
    raw = _run(_hashable(consts), n, dtype, jnp.asarray(comp, jnp.float32),
               jnp.asarray(comm, jnp.float32), jnp.asarray(wi),
               jnp.asarray(seeds))
    raw = jax.tree_util.tree_map(np.asarray, raw)
    out = []
    for i in range(len(points)):
        counts = raw["iter_counts"][i]
        its = raw["iter_times"][i].astype(np.float64)
        out.append(dict(
            iter_times=[its[j, :min(int(counts[j]), MAX_ITERS)]
                        for j in range(its.shape[0])],
            trace_util=raw["util"][i].astype(np.float64),
            trace_jobtput=raw["jobtput"][i].astype(np.float64),
            trace_t=raw["t"][i].astype(np.float64)))
    return out

