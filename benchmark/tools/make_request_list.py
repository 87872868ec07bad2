"""Writes the literal request list of a ``serve-closed`` traffic file.

    python3 benchmark/tools/make_request_list.py > /tmp/list.json
    python3 benchmark/tools/make_request_list.py --clients 16 \
        --per-client 12 --prompt 257 512 --output 256 640 --draw-seed 32

Pairs of (prompt tokens, output tokens) are drawn once, from a fixed seed,
uniformly from the stated ranges, and then the OUTPUT lengths are nudged
(inside their range) so that, counted in steps of the serving loop, no two
callers' requests are admitted within ``--separation`` steps of each other:
a caller's request of n tokens is admitted at step a, gets two tokens in
that step and one in each later step, and its next request is admitted at
a + n - 1.  Then "decode step plus one prefill" is one mode of the gap
distribution that holds clients / mean(n - 1) of the gaps, and two
prefills in one step, which would be a mode of their own above it, do not
happen.  Caller 0's first request is the shortest allowed (a window that
opens at the first completion opens soonest).

The defaults are ``gpt2xl-batch-decode``'s list
(``traffic/batch-decode-16-xlong-out.json``); the other lists' arguments
are in their files' ``drawn_from``.
"""

import argparse
import json

import numpy as np


def request_list(clients: int, per_client: int, prompt: tuple, output: tuple,
                 separation: int, draw_seed: int) -> dict:
    rng = np.random.default_rng(draw_seed)
    prompts = rng.integers(prompt[0], prompt[1] + 1, (per_client, clients))
    outputs = rng.integers(output[0], output[1] + 1, (per_client, clients))
    outputs[0, 0] = output[0]
    taken: list[int] = []          # admission steps fixed so far (k >= 1)
    at = np.zeros(clients, int)    # admission step of each caller's request
    for k in range(per_client):
        # callers in the order their request k ends
        for i in np.argsort(at + outputs[k]):
            n = int(outputs[k, i])
            for bump in sorted(range(output[0] - n, output[1] - n + 1),
                               key=abs):
                if k == 0 and i == 0 and bump:
                    continue
                nxt = at[i] + n + bump - 1
                if all(abs(nxt - t) >= separation for t in taken):
                    outputs[k, i] = n + bump
                    taken.append(int(nxt))
                    at[i] = nxt
                    break
            else:
                raise SystemExit("no admissible length; lower --separation")
    requests = [[int(prompts[k, i]), int(outputs[k, i])]
                for k in range(per_client) for i in range(clients)]
    return {"mean_output": float(outputs.mean()),
            "admissions_per_100_steps": 100 * len(taken) / max(taken),
            "requests": requests}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--per-client", type=int, default=14)
    ap.add_argument("--prompt", type=int, nargs=2, default=(129, 256))
    ap.add_argument("--output", type=int, nargs=2, default=(512, 750))
    ap.add_argument("--separation", type=int, default=4)
    ap.add_argument("--draw-seed", type=int, default=39)
    a = ap.parse_args(argv)
    print(json.dumps(request_list(a.clients, a.per_client, tuple(a.prompt),
                                  tuple(a.output), a.separation,
                                  a.draw_seed)))


if __name__ == "__main__":
    main()
