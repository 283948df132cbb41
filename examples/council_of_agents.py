"""Council of Agents — the paper's headline scenario, end to end.

A main "River" agent generates; [TASK: ...] triggers spawn side "Stream"
agents that reason over a landmark-compressed snapshot of the river's
context (Topological Synapse), pass the Validation Gate, and merge back via
Referential Injection — all sharing ONE copy of the weights (the Prism).

    PYTHONPATH=src python examples/council_of_agents.py
"""
from __future__ import annotations

import jax

from repro.configs import get_config
from repro.core.engine import CortexEngine
from repro.core.prism import Prism
from repro.data.tokenizer import ByteTokenizer
from repro.models import model as model_lib
from repro.serving.sampler import SamplingParams


def main():
    cfg = get_config("qwen2.5-0.5b", reduced=True)
    params = model_lib.init_params(jax.random.key(0), cfg)
    prism = Prism(params, cfg)
    tok = ByteTokenizer(cfg.vocab_size)
    engine = CortexEngine(
        prism,
        tok,
        n_main=2,
        max_side=4,
        main_capacity=512,
        side_max_steps=12,
        inject_tokens=8,
        theta=-1.0,  # untrained weights: accept all merges for the demo
        sampling=SamplingParams(temperature=1.0),
        # per-lane sampling: freshly spawned streams explore by default...
        side_sampling=SamplingParams(temperature=1.1, top_k=40),
        sync_every=4,  # ...and whole 4-tick windows ride ONE scanned dispatch
        # the pipelined drain (default) overlaps each window's router/decode
        # host work with the device's next window
    )
    # ...while river 0 decodes greedily — per-lane params share the dispatch
    engine.submit(
        "Research question: why is the sky blue? [TASK: check Rayleigh scattering] "
        "Let me think step by step.",
        lane=0,
        sampling=SamplingParams(greedy=True),
    )
    engine.submit("Second river: summarize the meeting notes. [TASK: list action items] ok", lane=1)

    for chunk in range(5):  # 5 pipelined chunks == 40 virtual ticks
        engine.run(8)  # drains overlap inside each chunk
        if chunk % 2 == 1:
            rep = engine.memory_report()
            st = engine.stats
            print(
                f"[tick {st['ticks']:3d}] agents={rep['n_agents']} "
                f"dispatches={st['tick_dispatches']} "
                f"(ticks/dispatch={st['ticks']/max(st['tick_dispatches'],1):.1f} "
                f"overlapped_drains={st['overlapped_drains']}) "
                f"weights={rep['weight_bytes']/1e6:.1f}MB "
                f"ctx/agent={rep['context_bytes_per_agent']/1e6:.2f}MB "
                f"total={rep['total_bytes']/1e6:.1f}MB "
                f"(standard-arch counterfactual: {rep['standard_architecture_bytes']/1e6:.1f}MB)"
            )

    print("\n--- event log ---")
    for e in engine.history:
        print(e)
    print("\n--- river 0 text (tail) ---")
    print(repr(engine.mains[0].text[-120:]))


if __name__ == "__main__":
    main()
