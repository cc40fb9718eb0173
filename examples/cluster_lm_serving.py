"""Distributed LM serving: prompts in the replicated store, decoded
across the cluster by the fair-share job pipeline.

    python examples/cluster_lm_serving.py --nodes 4 --prompts 8 --new-tokens 24

Spins a localhost cluster (UDP control plane + replicated store),
registers a small LM on every node (`JobService.register_lm`), PUTs
token-prompt files, runs `submit-job LM <N>` through the same
scheduler that serves image jobs — preemption, requeue-on-failure and
hot-standby relays included — and prints each prompt's completion
from the merged job output. Outputs are EXACTLY what an isolated
`generate()` would produce per prompt (the LMServer batching-
exactness contract, carried end-to-end through the cluster).

The reference has no sequence serving at all (SURVEY §0); this is the
distributed analog of its image pipeline for the framework's net-new
LM stack.
"""

import argparse
import asyncio
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


async def run(args) -> None:
    from dml_tpu.cluster.introducer import IntroducerService
    from dml_tpu.cluster.node import Node
    from dml_tpu.cluster.store_service import StoreService
    from dml_tpu.config import ClusterSpec, StoreConfig, Timing
    from dml_tpu.inference.lm_backend import LMBackend, write_prompt_file
    from dml_tpu.jobs.service import JobService

    # the SAME spec dict the CLI's --lm-spec flag consumes — one
    # source of truth for the deterministic build (LMBackend.from_spec)
    lm_spec = {
        "name": "LM",
        "vocab_size": args.vocab,
        "d_model": args.d_model,
        "n_heads": 4,
        "n_kv_heads": 2,
        "n_layers": args.layers,
        "d_ff": 4 * args.d_model,
        "dtype": "bfloat16" if args.bf16 else "float32",
        "max_new_tokens": args.new_tokens,
        "max_slots": 4,
        "max_len": args.max_len,
        "seed": 0,
    }

    tmp = tempfile.mkdtemp(prefix="dml_tpu_lm_cluster_")
    spec = ClusterSpec.localhost(
        args.nodes, base_port=args.base_port,
        introducer_port=args.base_port - 1,
        timing=Timing(ping_interval=0.2, ack_timeout=0.3,
                      cleanup_time=1.0, leader_rpc_timeout=10.0),
        store=StoreConfig(root=os.path.join(tmp, "roots"),
                          download_dir=os.path.join(tmp, "dl")),
    )
    dns = IntroducerService(spec)
    await dns.start()
    stack = []
    # ONE backend shared by every in-process node (the serve lock
    # serializes concurrent workers); N separate builds would hold N
    # weight copies for no reason in a single-process example
    be = LMBackend.from_spec(lm_spec)
    for n in spec.nodes:
        node = Node(spec, n)
        store = StoreService(node, root=os.path.join(tmp, f"st_{n.port}"))
        jobs = JobService(node, store)
        jobs.register_lm(
            lm_spec["name"], backend=be.backend, cost=be.cost()
        )
        await node.start()
        await store.start()
        await jobs.start()
        stack.append((node, store, jobs))
    try:
        for _ in range(100):
            if all(n.joined and n.leader_unique for n, _, _ in stack):
                break
            await asyncio.sleep(0.1)
        else:
            raise RuntimeError("cluster failed to converge")
        print(f"{args.nodes}-node cluster up; "
              f"leader={stack[0][0].leader_unique}")

        client_store, client_jobs = stack[-1][1], stack[-1][2]
        rng = np.random.RandomState(args.seed)
        for i in range(args.prompts):
            prompt = rng.randint(0, lm_spec["vocab_size"], rng.randint(4, 24))
            p = os.path.join(tmp, f"prompt_{i}.tokens.txt")
            write_prompt_file(p, prompt)
            await client_store.put(p, f"prompt_{i}.tokens.txt")
        print(f"PUT {args.prompts} prompt files (4-way replicated)")

        job_id = await client_jobs.submit_job("LM", args.prompts)
        done = await client_jobs.wait_job(job_id, timeout=600.0)
        print(f"job {job_id} complete: {done['total_queries']} prompts")
        merged = await client_jobs.get_output(
            job_id, os.path.join(tmp, "lm_output.json")
        )
        for fname in sorted(merged):
            toks = merged[fname]["tokens"]
            print(f"  {fname}: {' '.join(str(t) for t in toks)}")
        print("C1:", await _leader_c1(stack))
    finally:
        for node, store, jobs in reversed(stack):
            await jobs.stop()
            await store.stop()
            await node.stop()
        await dns.stop()


async def _leader_c1(stack):
    for n, _, j in stack:
        if n.is_leader:
            return j.scheduler.c1_stats()
    return {}


def main() -> None:
    from dml_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--prompts", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=24)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--base-port", type=int, default=29411)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--bf16", action="store_true")
    args = ap.parse_args()
    asyncio.run(run(args))


if __name__ == "__main__":
    main()
