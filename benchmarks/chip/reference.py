"""Plain reference for the replay engine's answers.

Given the trials a run replayed (the call seed and the trial's index in
the call), this module draws each trial's stream and random service
times from the key schedule of ``traffic_gen``, books its jobs one at a
time, in arrival order, by the rules of the deployment's configuration
file, and returns each job's response time and ``ok`` bit.  It imports
nothing of the program.

The data, in place of weights: a trial's key splits five ways (arrivals,
service, failure, overhead, priority); a fault-free deployment uses four:

* arrivals: the mix's law (``traffic_gen.poisson_arrivals``);
* service: unit-mean draws of shape ``(jobs, A + F, K)``, exponential
  or lognormal as the configuration's ``dist`` says; the first ``A``
  rows are shared by every member placed in that AZ, the last ``F``
  private to each member.  A member placed in AZ ``a`` runs its
  ``i``-th task ``k = seq[m][i]`` for
  ``(rho * S[a, k] + (1 - rho) * X[m, k]) * mean[k] + offset + stage``;
* overhead: Table-6 control-plane lognormals ``(jobs, F + 1)``; member 0
  joins after column 0, member ``m > 0`` after column 0 plus column
  ``m + 1`` (column 1 is drawn and unused);
* priority: uniforms ``(jobs, W)`` that break ties among free workers.

The draws are made on the device with the operations the service law
names, in their order: the schedule is chaotic, so one unit in the last
place of one draw moves later placements, and the key schedule and the
order of these operations are part of the contract the check enforces.

The rules (the paper's HA placement and flight race, workflows without
failures), on a cluster that is idle when the trial starts:

* placement, member by member: if no worker is free at the arrival, the
  member takes the earliest-free worker (first index on ties) and starts
  when it frees; otherwise it takes the free worker of highest priority,
  preferring workers in AZs the flight has not used yet.  A taken worker
  is not offered to the flight's later members;
* race: events are completions and, unless every first task is free of
  dependencies and the members' first tasks differ, joins; the earliest
  comes first, the lowest member on ties.  A completion marks its task
  done, and peers running the same task are preempted.  A member that
  joined, finished or was preempted takes the first task of its
  sequence not yet done: the finisher or joiner starts it at once, the
  others one stream latency later; a task whose dependencies are not
  all done parks the member until a later event wakes it; a member with
  no task left releases its worker.  The job completes when every task
  is done, and every member still holding a worker releases it then;
* a released worker's free-at time becomes the later of its old value
  and the release.

``precision="float32"`` is the configuration's own arithmetic, IEEE
single precision on every operation.  ``precision="bfloat16"`` is the
control: the same reference with every input and every operation rounded
to bfloat16, which the comparison must refuse.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

import traffic_gen

PRECISIONS = ("float32", "bfloat16")
DISTS = ("exp", "lognorm")


def overhead_params(median_ms: float, p90_ms: float):
    """(mu, sigma) of the lognormal with this median and 90th percentile
    (z of the 90th percentile: 1.2816; sigma floored at 0.05)."""
    mu = float(np.log(median_ms))
    return mu, max((float(np.log(p90_ms)) - mu) / 1.2816, 0.05)


@functools.partial(jax.jit,
                   static_argnames=("dist", "jobs", "W", "A", "F", "K"))
def _draw(keys, rate_hz, rho, cv, means, offset, stage, oh_mu, oh_sigma,
          seq, *, dist, jobs, W, A, F, K):
    """Every draw of the trials with these keys, ``keys`` (n, 2)."""

    def one(key):
        k_a, k_s, _, k_o, k_p = traffic_gen.split_trial_key(key)
        arrivals = traffic_gen.poisson_arrivals(k_a, jobs, rate_hz)
        if dist == "exp":
            sx = jax.random.exponential(k_s, (jobs, A + F, K))
        else:
            sigma2 = jnp.log1p(cv * cv)
            sx = jnp.exp(-sigma2 / 2 + jnp.sqrt(sigma2)
                         * jax.random.normal(k_s, (jobs, A + F, K)))
        s, x = sx[:, :A, :], sx[:, A:, :]
        z = ((rho * s[:, :, None, :] + (1 - rho) * x[:, None, :, :])
             * means + offset + stage)
        z = jnp.take_along_axis(z, jnp.broadcast_to(seq, (jobs, A, F, K)),
                                axis=3)
        oh = jnp.exp(oh_mu + oh_sigma
                     * jax.random.normal(k_o, (jobs, F + 1)))
        prio = jax.random.uniform(k_p, (jobs, W))
        return arrivals, z, oh, prio

    return jax.vmap(one)(keys)


class ReplayReference:
    """Books whole trials by the configuration's rules."""

    def __init__(self, config: dict, mix: dict,
                 precision: str = "float32"):
        if precision not in PRECISIONS:
            raise ValueError(f"precision must be one of {PRECISIONS}")
        m = config["service_model"]
        if m["dist"] not in DISTS or m.get("fail_prob", 0.0) != 0.0:
            raise ValueError(f"the reference books fault-free workflows "
                             f"with service laws {DISTS} only")
        traffic_gen.check_mix(mix)
        self.jobs, self.trials = int(mix["jobs"]), int(mix["trials"])
        self.rate_hz = float(mix["rate_hz"])
        self.W, self.A, self.F = (int(config["workers"]),
                                  int(config["azs"]), int(config["flight"]))
        self.seq = np.asarray(m["member_sequences"], dtype=np.int32)
        self.K = self.seq.shape[1]
        self.deps = [list(d) for d in m.get("depends_on",
                                            [[]] * self.K)]
        # members may begin on joining only if no first task waits on
        # another and the members' first tasks differ
        self.direct = (not any(self.deps)
                       and len({int(k) for k in self.seq[:, 0]}) == self.F)
        self.m = m
        self.oh_mu, self.oh_sigma = overhead_params(
            m["overhead_median_ms"], m["overhead_p90_ms"])
        if precision == "float32":
            self.q = np.float32
            self.qa = lambda a: np.asarray(a, dtype=np.float32)
        else:
            bf = ml_dtypes.bfloat16
            self.q = lambda v: np.float32(np.float32(v).astype(bf))
            self.qa = lambda a: np.asarray(
                np.asarray(a, dtype=np.float32).astype(bf), np.float32)
        self.w_az = np.arange(self.W) % self.A

    def draws(self, keys):
        """Draws of the trials with ``keys``, made ``trials`` at a time,
        the shape of one call of the program."""
        m = self.m
        f32 = jnp.float32
        n = keys.shape[0]
        pad = -n % self.trials
        keys = jnp.concatenate([keys, jnp.repeat(keys[-1:], pad, axis=0)])
        out = []
        for c0 in range(0, keys.shape[0], self.trials):
            d = _draw(keys[c0:c0 + self.trials], f32(self.rate_hz),
                      f32(m["rho"]), f32(m["cv"]),
                      jnp.asarray(m["means_ms"], f32), f32(m["offset_ms"]),
                      f32(m["stage_ms"]), f32(self.oh_mu),
                      f32(self.oh_sigma), jnp.asarray(self.seq),
                      dist=m["dist"], jobs=self.jobs, W=self.W, A=self.A,
                      F=self.F, K=self.K)
            out.append([np.asarray(a) for a in d])
        return [np.concatenate(parts)[:n] for parts in zip(*out)]

    def run(self, samples):
        """Book the trials ``samples``, a list of ``(call_seed, trial)``.
        Returns ``(resp_ms, ok)``, each ``(len(samples), jobs)``."""
        if not samples:
            return (np.empty((0, self.jobs), np.float32),
                    np.empty((0, self.jobs), bool))
        keys = jnp.stack([traffic_gen.trial_keys(s, self.trials)[t]
                          for s, t in samples])
        arrivals, z, oh, prio = self.draws(keys)
        resp, ok = [], []
        for i in range(len(samples)):
            r, o, _ = self._book(arrivals[i], z[i], oh[i], prio[i],
                                 np.zeros(self.W, np.float32))
            resp.append(r)
            ok.append(o)
        return np.stack(resp), np.stack(ok)

    def _book(self, arr, z, oh, prio, wf):
        q, qa = self.q, self.qa
        F, W = self.F, self.W
        seq = self.seq.tolist()
        slat = q(self.m["stream_latency_ms"])
        arr = qa(arr)
        z = qa(z)
        # member 0 joins after the arrival overhead, member m > 0 after
        # that plus a second control-plane hop, its own column m + 1
        t_oh = np.empty((oh.shape[0], F), np.float32)
        t_oh[:, 0] = qa(oh[:, 0])
        for mm in range(1, F):
            t_oh[:, mm] = qa(qa(oh[:, 0]) + qa(oh[:, mm + 1]))
        p_fresh = qa(qa(prio) + np.float32(1.0))
        p_any = qa(prio)
        w_az = self.w_az
        # other_az[a]: workers outside AZ a, for the fresh-AZ preference
        other_az = [w_az != az for az in range(self.A)]
        wf = qa(wf).copy()
        n = arr.shape[0]
        resp = np.empty(n, np.float32)
        ok = np.ones(n, dtype=bool)
        inf = np.float32(np.inf)
        minus_one = np.float32(-1.0)
        for j in range(n):
            a = arr[j]
            fresh = None                       # every AZ unused so far
            workers, held, t_join, m_az = [], [], [], []
            for mm in range(F):
                w_min = int(wf.argmin())
                t_any = wf[w_min]
                if t_any > a:
                    w = w_min
                else:
                    pref = (p_fresh[j] if fresh is None else
                            np.where(fresh, p_fresh[j], p_any[j]))
                    w = int(np.where(wf <= a, pref, minus_one).argmax())
                az = int(w_az[w])
                fresh = (other_az[az] if fresh is None
                         else fresh & other_az[az])
                workers.append(w)
                held.append(wf[w])
                m_az.append(az)
                t_join.append(q(max(a, t_any) + t_oh[j, mm]))
                wf[w] = inf                    # not offered again
            t_resp, ok[j], t_rel = self._race(
                [z[j, m_az[mm], mm] for mm in range(F)], t_join, seq, slat)
            resp[j] = q(t_resp - a)
            for mm, (w, old) in enumerate(zip(workers, held)):
                wf[w] = max(old, t_rel[mm])
        return resp, ok, wf

    def _race(self, zs, t_join, seq, slat):
        """One flight's race; returns ``(t_resp, ok, t_release)``, the
        flight's completion and each member's release time."""
        q = self.q
        F, K = len(zs), len(seq[0])
        deps = self.deps
        inf = np.float32(np.inf)
        done = [False] * K
        if self.direct:
            # every first task is dependency-free and the members' first
            # tasks differ: each member starts its first task on joining
            cur = [seq[mm][0] for mm in range(F)]
            fin = [q(t_join[mm] + zs[mm][0]) for mm in range(F)]
        else:
            # joins are events of their own (task -1)
            cur = [-1] * F
            fin = list(t_join)
        released = [False] * F
        t_rel = [np.float32(0.0)] * F
        while True:
            t = min(fin)
            e = fin.index(t)
            task = cur[e]
            if task >= 0:
                done[task] = True
            busy = [f != inf for f in fin]
            # the finisher, and peers preempted mid-task by its broadcast
            freed = [mm == e or (task >= 0 and busy[mm] and cur[mm] == task)
                     for mm in range(F)]
            for mm in range(F):
                if busy[mm] and not freed[mm]:
                    continue                   # still busy on its task
                if released[mm]:
                    continue
                # next task: the first of its sequence not yet done
                nxt = next((i for i, k in enumerate(seq[mm])
                            if not done[k]), None)
                if nxt is None:
                    fin[mm], cur[mm] = inf, -1
                    released[mm], t_rel[mm] = True, t
                    continue
                k = seq[mm][nxt]
                if all(done[d] for d in deps[k]):
                    # the finisher chains at once; others one stream
                    # latency after the broadcast
                    start = t if mm == e else q(t + slat)
                    fin[mm], cur[mm] = q(start + zs[mm][nxt]), k
                else:
                    fin[mm], cur[mm] = inf, -1     # parks until woken
            complete = all(done)
            if complete or all(f == inf for f in fin):
                for mm in range(F):
                    if not released[mm]:
                        t_rel[mm] = t
                return t, complete, t_rel


def compare(prog, ref) -> dict:
    """The numbers ``correct`` is decided on, program against reference:
    ``prog``/``ref`` are ``(resp_ms, ok)`` of the same trials.
    ``mismatched_jobs`` counts jobs whose response or ok bit differs from
    the reference's, or that got no finite response; ``resp_gap_ms`` is
    the widest gap of a response.  ``unbooked`` (jobs with no finite
    response) is reported as failed."""
    p_resp, p_ok = (np.asarray(x) for x in prog)
    r_resp, r_ok = (np.asarray(x) for x in ref)
    n = r_resp.size
    if p_resp.shape != r_resp.shape:
        return {"unbooked": abs(n - p_resp.size), "mismatched_jobs": n,
                "resp_gap_ms": np.inf}
    p_resp = p_resp.astype(np.float64)
    r_resp = r_resp.astype(np.float64)
    booked = np.isfinite(p_resp)
    with np.errstate(invalid="ignore"):
        gap = np.abs(p_resp - r_resp)
    gap[booked != np.isfinite(r_resp)] = np.inf
    gap = np.nan_to_num(gap, nan=np.inf)
    return {"unbooked": int(np.count_nonzero(~booked)),
            "mismatched_jobs": int(np.count_nonzero((gap != 0.0)
                                                    | (p_ok != r_ok))),
            "resp_gap_ms": float(gap.max()) if n else 0.0}
