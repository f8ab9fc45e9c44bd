"""Monte-Carlo routing correlation study for the MoE decode dedup default.

`--moe-decode-dedup`'s two-tier lax.cond pays off iff the
runtime unique-expert count u of a decode batch fits the small grid
(u <= U_small = lanes*k/2). Whether that happens depends on routing
correlation across lanes, which no synthetic fixture exhibits and no real
checkpoint is reachable (zero egress). This sim maps the DECISION
BOUNDARY instead: for A3B shapes (E=128, k=8), how correlated must lane
routing be before the small grid hits most of the time?

Model: lane l's gate logits z_l = sqrt(rho) * g_shared + sqrt(1-rho) *
g_l + bias, g ~ N(0, I_E); bias_e = -s * log(rank_e) imposes a Zipf-like
expert popularity (s = 0 balanced, s = 1 strongly skewed — aux-loss-
balanced MoEs sit near 0..0.5 corpus-wide). rho models shared-prefix /
same-domain lanes. u = |union of per-lane top-k|.

Prints a table of E[u] and P(u <= U_small) over (batch, rho, s); the
conclusion lives in docs/moe_decode_dedup.md.
"""

import json
import sys

import numpy as np

E, K = 128, 8
TRIALS = 4000


def sim(batch: int, rho: float, s: float, rng) -> tuple[float, float]:
    cap = batch * K // 2
    bias = -s * np.log(np.arange(1, E + 1, dtype=np.float64))
    us = np.empty(TRIALS, np.int64)
    for t in range(TRIALS):
        shared = rng.standard_normal(E)
        z = (
            np.sqrt(rho) * shared[None, :]
            + np.sqrt(1.0 - rho) * rng.standard_normal((batch, E))
            + bias[None, :]
        )
        top = np.argpartition(z, -K, axis=1)[:, -K:]
        us[t] = np.unique(top).size
    return float(us.mean()), float((us <= cap).mean())


def main() -> None:
    rng = np.random.default_rng(0)
    rows = []
    for batch in (4, 8, 16):
        for rho in (0.0, 0.5, 0.8, 0.9, 0.95, 0.99):
            for s in (0.0, 0.5, 1.0):
                mean_u, hit = sim(batch, rho, s, rng)
                rows.append(
                    dict(batch=batch, rho=rho, zipf_s=s, cap=batch * K // 2,
                         mean_u=round(mean_u, 1), hit_rate=round(hit, 3))
                )
    print(json.dumps(rows))
    # human table on stderr
    print(f"{'B':>3} {'rho':>5} {'s':>4} {'cap':>4} {'E[u]':>6} {'P(hit)':>7}",
          file=sys.stderr)
    for r in rows:
        print(
            f"{r['batch']:>3} {r['rho']:>5} {r['zipf_s']:>4} {r['cap']:>4} "
            f"{r['mean_u']:>6} {r['hit_rate']:>7}",
            file=sys.stderr,
        )


if __name__ == "__main__":
    main()
