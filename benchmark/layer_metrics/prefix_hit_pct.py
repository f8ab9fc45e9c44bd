"""Share of the window's prompt tokens adopted from the prefix pool. Near
zero by design where prompts share only the chat template: the control for
a later shared-prefix cell."""
from benchmark.harness import rundir

LAYER, UNIT, BETTER, SOURCE, MOVES = "KV pool", "%", "higher", "program_counter", "ttft_mean_ms"


def read(run_dir):
    recs = [r for r in rundir.server_records(run_dir) if r["n_prompt_tokens"]]
    if not recs:
        return None
    return 100.0 * sum(r["reused_prefix_tokens"] for r in recs) / sum(
        r["n_prompt_tokens"] for r in recs)
