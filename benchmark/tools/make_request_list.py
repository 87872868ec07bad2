"""Writes the literal request list of a ``serve-closed`` traffic file.

    python3 benchmark/tools/make_request_list.py > /tmp/list.json

Pairs of (prompt tokens, output tokens) are drawn once, from a fixed seed,
uniformly from the stated ranges, and then the OUTPUT lengths are nudged
(inside their range) so that, counted in steps of the serving loop, no two
callers' requests are admitted within ``SEPARATION`` steps of each other:
a caller's request of n tokens is admitted at step a, gets two tokens in
that step and one in each later step, and its next request is admitted at
a + n - 1.  Then "decode step plus one prefill" is one mode of the gap
distribution that holds clients / mean(n - 1) of the gaps, and two
prefills in one step, which would be a mode of their own above it, do not
happen.  Caller 0's first request is the shortest allowed, because the
window opens at the first completion.
"""

import json

import numpy as np

CLIENTS = 16
PER_CLIENT = 12
PROMPT = (129, 256)
OUTPUT = (64, 192)
SEPARATION = 4
DRAW_SEED = 24


def main() -> None:
    rng = np.random.default_rng(DRAW_SEED)
    prompts = rng.integers(PROMPT[0], PROMPT[1] + 1, (PER_CLIENT, CLIENTS))
    outputs = rng.integers(OUTPUT[0], OUTPUT[1] + 1, (PER_CLIENT, CLIENTS))
    outputs[0, 0] = OUTPUT[0]
    taken: list[int] = []          # admission steps fixed so far (k >= 1)
    at = np.zeros(CLIENTS, int)    # admission step of each caller's request
    for k in range(PER_CLIENT):
        # callers in the order their request k ends
        for i in np.argsort(at + outputs[k]):
            n = int(outputs[k, i])
            for bump in sorted(range(OUTPUT[0] - n, OUTPUT[1] - n + 1),
                               key=abs):
                if k == 0 and i == 0 and bump:
                    continue
                nxt = at[i] + n + bump - 1
                if all(abs(nxt - t) >= SEPARATION for t in taken):
                    outputs[k, i] = n + bump
                    taken.append(int(nxt))
                    at[i] = nxt
                    break
            else:
                raise SystemExit("no admissible length; lower SEPARATION")
    requests = [[int(prompts[k, i]), int(outputs[k, i])]
                for k in range(PER_CLIENT) for i in range(CLIENTS)]
    print(json.dumps({"mean_output": float(outputs.mean()),
                      "admissions_per_100_steps": 100 * len(taken) / max(taken),
                      "requests": requests}))


if __name__ == "__main__":
    main()
