"""Model-server entry point.

Parity: the reference's server launch path
(``mega_triton_kernel/test/models/model_server.py`` ``__main__``).
Beyond parity, ``--replicas N`` stands the multi-engine serving tier
up behind the same socket: N ``ContinuousEngine`` replicas behind the
prefix-affinity router (docs/scale-out.md), served by the same wire
protocol (``requests`` payloads only — the router speaks continuous
batching).

It is also the process-fleet replica entry (docs/scale-out.md
"Process fleet"): ``serving/supervisor.py`` spawns one of these per
replica with ``--port-file`` (the child binds port 0 and writes the
address it got, atomically, for the supervisor to pick up) and — in
tests and the fleet bench — ``--model stub``, which serves the
deterministic :class:`~triton_distributed_tpu.models.stub.StubEngine`
(real radix control plane, hash-function "model", no JAX model load)
behind the production wire server.

Usage:
    python -m triton_distributed_tpu.serving.run_server \
        --model tiny --tp 1 --port 8765
    python -m triton_distributed_tpu.serving.run_server \
        --model tiny --replicas 2 --policy affinity
    python -m triton_distributed_tpu.serving.run_server \
        --model stub --port-file /tmp/r0.port --stub-delay 0.2
"""

from __future__ import annotations

import argparse
import itertools
import os
import sys

import jax


def resolve_model_args(
    model: str, num_experts: int = 0, top_k: int = 0,
    moe_intermediate: int = 0, *, num_layers: int = 0,
    first_k_dense: int = 0, experts_held: int = 0, expert_offset: int = 0,
    vocab_rows: int = 0,
) -> tuple[str, dict]:
    """``--model moe`` alias resolution (ONE definition for main and
    tests): the tiny-moe Qwen3MoE preset, with the expert knobs as
    config overrides. Non-moe names pass through with the same
    overrides applied (an MoE checkpoint dir can be resized too).

    The keyword knobs cut a published preset to one rank's share of a
    deployment (docs/serving.md "Latent attention and one rank's
    share"): the layers of this pipeline stage, how many of them are
    leading dense ones, the routed experts held here and where they
    start, the vocabulary rows held here. Widths are never cut."""
    name = "tiny-moe" if model == "moe" else model
    overrides: dict = {}
    for key, value in (
        ("num_experts", num_experts),
        ("num_experts_per_tok", top_k),
        ("moe_intermediate_size", moe_intermediate),
        ("num_layers", num_layers),
        ("first_k_dense", first_k_dense),
        ("experts_held", experts_held),
        ("expert_offset", expert_offset),
        ("vocab_size", vocab_rows),
    ):
        if value:
            overrides[key] = value
    return name, overrides


def _write_port_file(path: str | None, host: str, port: int) -> None:
    """Atomic port handshake: the supervisor polls for PATH, so the
    write must never be observable half-done — write a sibling temp
    file, then rename (atomic on POSIX)."""
    if not path:
        return
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        f.write(f"{host}:{port}\n")
    os.replace(tmp, path)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", default="tiny",
                   help="model preset, checkpoint dir, 'stub', or "
                   "'moe' (the tiny-moe Qwen3MoE preset; size it with "
                   "--num-experts/--top-k/--moe-intermediate — "
                   "docs/serving.md 'MoE serving')")
    p.add_argument("--num-experts", type=int, default=0,
                   help="override the MoE preset's expert count "
                   "(routed experts; must divide by --tp for "
                   "--mode mega's EP sharding)")
    p.add_argument("--top-k", type=int, default=0,
                   help="override the MoE preset's experts-per-token")
    p.add_argument("--moe-intermediate", type=int, default=0,
                   help="override the MoE preset's per-expert FFN width")
    p.add_argument("--num-layers", type=int, default=0,
                   help="serve this many of the preset's layers (one "
                   "pipeline stage's; docs/serving.md 'Latent attention "
                   "and one rank's share')")
    p.add_argument("--first-k-dense", type=int, default=0,
                   help="how many of --num-layers are leading dense "
                   "layers (presets with expert layers after dense ones)")
    p.add_argument("--experts-held", type=int, default=0,
                   help="routed experts whose weights this rank holds: "
                   "the router keeps every output, rows for experts "
                   "held elsewhere are dropped")
    p.add_argument("--expert-offset", type=int, default=0,
                   help="first routed expert held here")
    p.add_argument("--vocab-rows", type=int, default=0,
                   help="rows of the embedding and columns of the head "
                   "held here (a vocabulary-parallel slice)")
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--host", default="127.0.0.1",
                   help="bind address; 0.0.0.0 listens on every "
                   "interface (pair with --advertise-host so peers "
                   "get a ROUTABLE address, docs/scale-out.md "
                   "'Multi-host fleet')")
    p.add_argument("--advertise-host", default=None, metavar="ADDR",
                   help="the address OTHER machines reach this server "
                   "at — written to the --port-file handshake, "
                   "reported in server_stats, and broadcast in fabric "
                   "peer tables instead of the bind address (which "
                   "with --host 0.0.0.0 is unroutable). Default: the "
                   "bind address.")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--mode", default="xla",
                   choices=["xla", "pallas", "mega"])
    p.add_argument("--ns", type=int, default=8,
                   help="with --mode mega: tokens fused per decode "
                   "launch (the NS-step chunk; docs/megakernel.md "
                   "'Serving fast path'). Larger NS amortizes more "
                   "host dispatch per token at coarser admission "
                   "granularity; perf/mega_serve_bench.py sweeps it.")
    p.add_argument("--resident", action="store_true",
                   help="with --mode mega: resident decode — pipeline "
                   "round i+1's launch before draining round i "
                   "(docs/megakernel.md 'Resident decode'). "
                   "Continuous-batching engines only.")
    p.add_argument("--kv-dtype", default=None, choices=["int8"],
                   help="int8-quantized paged KV pool (docs/serving.md "
                   "'Quantized KV cache'); composes with every --mode "
                   "including mega (in-kernel dequant). The single-"
                   "Engine path then serves paged.")
    p.add_argument("--speculative", type=int, default=0, metavar="K",
                   help="self-drafting speculative decoding, up to K "
                   "draft tokens per row (docs/serving.md 'Speculative "
                   "decoding'); excluded with --mode mega — the NS-step "
                   "fused launch already amortizes dispatch")
    p.add_argument("--replicas", type=int, default=0,
                   help="serve N ContinuousEngine replicas behind the "
                   "prefix-affinity router (0 = single fixed-batch "
                   "Engine, the legacy path); docs/scale-out.md")
    p.add_argument("--fleet", type=int, default=0,
                   help="boot a SUPERVISED PROCESS fleet of N run_server "
                   "children (FleetSupervisor: heartbeats, crash "
                   "respawn, snapshot-based recovery — docs/scale-out.md "
                   "'Process fleet') and serve the router in THIS "
                   "process; children inherit --model/--mode/--kv-dtype/"
                   "--speculative/--ns/--resident/--max-batch (or the "
                   "--stub-* knobs with --model stub)")
    p.add_argument("--continuous", action="store_true",
                   help="serve ONE ContinuousEngine (continuous "
                   "batching, 'requests' payloads) instead of the "
                   "fixed-batch Engine — the process-fleet child shape")
    p.add_argument("--policy", default=None,
                   choices=["affinity", "round_robin",
                            "migrate_after_prefill", "pools"],
                   help="router policy with --replicas/--fleet "
                   "(migrate_after_prefill = prefill→decode handoff; "
                   "pools = role-aware placement over prefill/decode "
                   "pools, docs/scale-out.md 'Disaggregated pools & "
                   "autoscaling'). Default: affinity, or pools when "
                   "--prefill-replicas/--decode-replicas shape the "
                   "fleet")
    p.add_argument("--prefill-replicas", type=int, default=0,
                   help="boot a ROLE-TYPED process fleet: N children "
                   "tagged prefill (fresh requests land here; the "
                   "pools policy hands their slots to the decode pool "
                   "after the first token — docs/scale-out.md "
                   "'Disaggregated pools & autoscaling'). Goes with "
                   "--decode-replicas; sizes the fleet itself, so "
                   "drop --fleet N")
    p.add_argument("--decode-replicas", type=int, default=0,
                   help="role-typed fleet: N children tagged decode "
                   "(migrated post-prefill slots decode here, placed "
                   "by digest-match vs pool pressure)")
    p.add_argument("--autoscale", action="store_true",
                   help="run the goodput-driven pool autoscaler over "
                   "the role-typed fleet (scale-up spawns role-tagged "
                   "children, scale-down drains losslessly; bounds "
                   "[N, N+2] per pool) — needs --prefill-replicas/"
                   "--decode-replicas")
    p.add_argument("--snapshot-every", type=int, default=0,
                   help="ContinuousEngine incremental slot snapshots "
                   "every N scheduling rounds (0 = off) — the "
                   "export_slots verb's crash-recovery feed "
                   "(docs/scale-out.md 'Slot migration & handoff')")
    p.add_argument("--tier-bytes", type=int, default=0,
                   help="host-RAM durable KV tier capacity in bytes "
                   "per engine (0 = off): evicted radix pages spill "
                   "to the tier and fault back on digest match, "
                   "cheaper than re-prefill (docs/serving.md 'Tiered "
                   "KV'); applies to --continuous/--replicas engines "
                   "and is inherited by --fleet children")
    p.add_argument("--tier-dir", default=None, metavar="DIR",
                   help="disk tier directory (write-through, atomic "
                   "rename, checksummed entries): spilled pages AND "
                   "the snapshot buffer survive a process restart. "
                   "With --replicas/--fleet each engine gets DIR/r<i> "
                   "unless --tier-shared makes DIR one fleet-wide "
                   "fabric dir; with --fleet the supervisor also "
                   "persists pulled snapshots under DIR/resume, so ONE "
                   "flag boots a restart-safe fleet (docs/scale-out.md "
                   "'Durable snapshots')")
    p.add_argument("--tier-shared", action="store_true",
                   help="share ONE KV tier across the replicas instead "
                   "of per-engine DIR/r<i> splits (docs/scale-out.md "
                   "'KV fabric'): with --fleet every child mounts the "
                   "same --tier-dir (digest-keyed, checksummed entries "
                   "make concurrent writers safe, and a fresh "
                   "autoscaler replica boots warm from the pool's "
                   "spills); with --replicas the engines share one "
                   "in-process PageStore")
    p.add_argument("--hosts", default=None, metavar="H1,H2,...",
                   help="with --fleet/--prefill-replicas: spread the "
                   "children across these ssh-reachable hosts "
                   "(SSHLauncher, docs/scale-out.md 'Multi-host "
                   "fleet'); replicas are assigned round-robin and "
                   "the supervisor treats each host as a failure "
                   "domain (whole-host loss classifies as ONE "
                   "host_down, survivors are re-placed)")
    p.add_argument("--fake-hosts", type=int, default=0, metavar="N",
                   help="with --fleet/--prefill-replicas: partition "
                   "the LOCAL children into N named fake hosts "
                   "(process groups h0..h{N-1}) so host-loss "
                   "semantics run without real ssh — the chaos-suite "
                   "and host_loss_bench shape")
    p.add_argument("--connect-timeout", type=float, default=10.0,
                   help="supervisor-side dial timeout in seconds for "
                   "replica connections (cross-host dials to a dead "
                   "machine fail on THIS deadline instead of the OS "
                   "default)")
    p.add_argument("--snapshot-s", type=float, default=0.0,
                   help="with --fleet: supervisor snapshot-pull period "
                   "in seconds (0 = off) — failed replicas' requests "
                   "then resume from the last snapshot instead of "
                   "replaying from the prompt")
    p.add_argument("--max-batch", type=int, default=4,
                   help="decode slots per replica with --replicas")
    p.add_argument("--drain-grace", type=float, default=2.0,
                   help="drain grace (seconds) for server connections "
                   "AND router replica drains")
    p.add_argument("--request-timeout", type=float, default=0.0,
                   help="with --replicas: router-observed replica "
                   "timeout in seconds — a replica sitting on a "
                   "request this long is marked dead and the request "
                   "re-routed (0 = off, the default: a cold first "
                   "request compiles for minutes and must not read as "
                   "a hang)")
    p.add_argument("--port-file", default=None, metavar="PATH",
                   help="after binding, atomically write 'host:port' "
                   "to PATH — the supervisor's port-discovery "
                   "handshake for children launched with --port 0 "
                   "(docs/scale-out.md 'Process fleet')")
    p.add_argument("--stub-delay", type=float, default=0.0,
                   help="with --model stub: per-batch wall-time floor "
                   "in seconds (holds a batch in flight so chaos "
                   "tests can kill the process mid-batch)")
    p.add_argument("--stub-pages", type=int, default=256,
                   help="with --model stub: page-pool size")
    p.add_argument("--stub-page-size", type=int, default=16,
                   help="with --model stub: tokens per page")
    p.add_argument("--stub-max-batch", type=int, default=0,
                   help="with --model stub: decode-slot capacity per "
                   "continuous-batching round (an N-request batch "
                   "costs ceil(N/cap) rounds of --stub-delay wall "
                   "time; 0 = unbounded). Gives a stub replica FINITE "
                   "throughput so capacity benches can saturate it "
                   "(perf/pools_bench.py)")
    p.add_argument("--slo-ttft-ms", type=float, default=0.0,
                   help="default-class SLO deadline on WIRE-side time "
                   "to first token, milliseconds (0 = unbounded); the "
                   "{'cmd':'slo'} verb reports goodput against it "
                   "(docs/observability.md 'SLO goodput')")
    p.add_argument("--slo-tpot-ms", type=float, default=0.0,
                   help="default-class SLO deadline on wire-side "
                   "per-token time, milliseconds (0 = unbounded)")
    p.add_argument("--slo-e2e-ms", type=float, default=0.0,
                   help="default-class SLO deadline on wire-side "
                   "end-to-end latency, milliseconds (0 = unbounded)")
    p.add_argument("--trace", default=None, metavar="DIR",
                   help="wrap the whole run in group_profile(DIR) and "
                   "merge ONE chrome timeline on exit — host "
                   "trace_spans plus, with --mode mega, the device "
                   "task tracer's per-task rows (docs/profiling.md "
                   "'Device task tracer'); prints the merged path. "
                   "Also turns the engines' kernel_trace knob on and "
                   "surfaces both in server_stats.")
    args = p.parse_args(argv)
    if args.speculative and args.mode == "mega":
        # Explicit, named-knob refusal naming the ACTUAL conflicting
        # pair — speculative × mega — and fired BEFORE any model-name
        # resolution so every --model (qwen/moe/stub) gets the same
        # named-flag message instead of whatever resolve_model_args
        # surfaces first. (The engines raise the same conflict; failing
        # at the CLI names the flags to change.)
        p.error(
            "--speculative and --mode mega do not compose: the "
            "megakernel's NS-step fused launch advances all slots in "
            "lockstep and already amortizes per-step dispatch, and "
            "the resident pipeline splices whole slots between "
            "rounds — never a mid-launch verify/rollback "
            "(docs/megakernel.md 'Resident decode'). Drop "
            "--speculative or use --mode xla/pallas."
        )
    if args.resident and args.mode != "mega":
        # Same fail-fast convention: resident decode IS the megakernel's
        # pipelined round loop — there is nothing to make resident on
        # the xla/pallas paths, and silently ignoring the flag would
        # leave an operator believing the pipelined dispatch is on.
        p.error("--resident requires --mode mega (resident decode is "
                "the megakernel's pipelined round loop; "
                "docs/megakernel.md 'Resident decode')")
    if args.ns < 1:
        p.error("--ns must be >= 1")
    # --model moe: the Qwen3MoE serving alias (tiny-moe preset so a
    # laptop/CI run needs no checkpoint), sized by the knob overrides.
    model_name, overrides = resolve_model_args(
        args.model, args.num_experts, args.top_k, args.moe_intermediate,
        num_layers=args.num_layers, first_k_dense=args.first_k_dense,
        experts_held=args.experts_held, expert_offset=args.expert_offset,
        vocab_rows=args.vocab_rows,
    )
    if (args.tier_bytes or args.tier_dir) and args.fleet == 0 and (
            args.model == "stub"
            or not (args.replicas or args.continuous)):
        # Same fail-fast convention: the fixed-batch Engine (and the
        # single stub server) has no tier — silently ignoring the
        # flags would leave an operator believing restart-safety is on.
        p.error(
            "--tier-bytes/--tier-dir ride the continuous serving "
            "stack only (docs/serving.md 'Tiered KV'): add "
            "--continuous, --replicas N, or --fleet N."
        )
    if args.tier_bytes and args.fleet > 0 and args.model == "stub":
        p.error(
            "--tier-bytes does nothing on a stub fleet (stub children "
            "have no KV tier); --tier-dir still arms the supervisor's "
            "durable resume store, or use a real --model."
        )
    if args.tier_shared and (args.hosts or args.fake_hosts):
        # A shared tier dir is files on ONE machine's disk; children
        # on another host would mount a path that isn't there (or
        # worse, a same-named local dir holding nothing). Refuse by
        # flag name — the cross-host KV path is the wire fabric, which
        # per-child tiers get for free from the supervisor's
        # tier_peers broadcast.
        p.error(
            "--tier-shared shares a tier through ONE host's "
            "filesystem and cannot cross --hosts/--fake-hosts "
            "boundaries; drop --tier-shared (per-child --tier-dir "
            "tiers reach each other over the wire KV fabric, "
            "docs/scale-out.md 'KV fabric')."
        )
    if args.tier_shared:
        # Same fail-fast-by-flag-name convention: a shared tier only
        # means something when there are multiple engines to share it.
        many = (args.fleet > 0 or args.replicas > 1
                or args.prefill_replicas > 0 or args.decode_replicas > 0)
        if not many:
            p.error(
                "--tier-shared shares ONE KV tier ACROSS replicas "
                "(docs/scale-out.md 'KV fabric'); add --fleet N, "
                "--replicas N (N >= 2), or the --prefill-replicas/"
                "--decode-replicas pool shape."
            )
        if args.model == "stub" and args.replicas == 0:
            p.error(
                "--tier-shared does nothing on a stub fleet (stub "
                "children have no KV tier); use a real --model."
            )
        if (args.fleet > 0 or args.prefill_replicas > 0
                or args.decode_replicas > 0) and not args.tier_dir:
            p.error(
                "--tier-shared on a PROCESS fleet shares through disk "
                "— the children are separate processes, so give the "
                "common directory with --tier-dir DIR."
            )
        if args.replicas > 1 and not (args.tier_bytes or args.tier_dir):
            p.error(
                "--tier-shared needs a tier to share: add --tier-bytes "
                "N and/or --tier-dir DIR."
            )
    # Role-typed pools (docs/scale-out.md "Disaggregated pools &
    # autoscaling") — fail-fast by flag name on every path that would
    # silently ignore them (the PR 12 guardrail convention).
    pool_fleet = args.prefill_replicas > 0 or args.decode_replicas > 0
    if pool_fleet:
        if args.prefill_replicas <= 0 or args.decode_replicas <= 0:
            p.error(
                "--prefill-replicas and --decode-replicas go together "
                "(a one-role fleet has nowhere to hand prefilled "
                "slots); give both, each >= 1."
            )
        if args.fleet:
            p.error(
                "--prefill-replicas/--decode-replicas size the fleet "
                "themselves (prefill+decode children); drop --fleet N."
            )
        if args.replicas or args.continuous:
            p.error(
                "--prefill-replicas/--decode-replicas are PROCESS-"
                "fleet pool shapes; --replicas/--continuous serve "
                "in-process engines that would silently ignore the "
                "role tags. Drop those flags."
            )
        if args.policy not in (None, "pools"):
            p.error(
                f"--policy {args.policy} ignores replica roles; a "
                "role-typed fleet routes with --policy pools (the "
                "default when --prefill-replicas/--decode-replicas "
                "are given)."
            )
    if args.autoscale and not pool_fleet:
        p.error(
            "--autoscale resizes role pools: add --prefill-replicas N "
            "and --decode-replicas M (docs/scale-out.md "
            "'Disaggregated pools & autoscaling')."
        )
    if args.hosts and args.fake_hosts:
        p.error(
            "--hosts and --fake-hosts are rival launchers (real ssh "
            "spawns vs local process-group fakes); give one."
        )
    if (args.hosts or args.fake_hosts) and not (
            args.fleet > 0 or pool_fleet):
        p.error(
            "--hosts/--fake-hosts place PROCESS-fleet children on "
            "failure domains; add --fleet N or the "
            "--prefill-replicas/--decode-replicas pool shape "
            "(docs/scale-out.md 'Multi-host fleet')."
        )
    if args.fake_hosts < 0:
        p.error("--fake-hosts takes N >= 1 fake hosts.")
    policy = args.policy or ("pools" if pool_fleet else "affinity")

    from triton_distributed_tpu.serving.server import ModelServer

    # Default-class SLO deadlines (docs/observability.md "SLO
    # goodput"): the FRONT server judges wire-side timelines against
    # these; fleet children never need them (their batches are
    # internal fan-out and skip the ledger).
    slo = None
    if args.slo_ttft_ms or args.slo_tpot_ms or args.slo_e2e_ms:
        from triton_distributed_tpu.obs.slo import SLOSpec

        slo = SLOSpec(
            "default",
            ttft_s=(args.slo_ttft_ms / 1e3) if args.slo_ttft_ms else None,
            tpot_s=(args.slo_tpot_ms / 1e3) if args.slo_tpot_ms else None,
            e2e_s=(args.slo_e2e_ms / 1e3) if args.slo_e2e_ms else None,
        )

    if args.fleet > 0 or pool_fleet:
        # Supervised process fleet (docs/scale-out.md "Process
        # fleet"): N run_server children under the FleetSupervisor,
        # the router served from THIS process — no model loads here.
        # --prefill-replicas/--decode-replicas shape the same fleet
        # into role-typed pools (docs/scale-out.md "Disaggregated
        # pools & autoscaling").
        from triton_distributed_tpu.serving.supervisor import (
            FleetSupervisor,
            ReplicaSpec,
            stub_spec,
        )

        if pool_fleet:
            members = (
                [(f"p{i}", "prefill")
                 for i in range(args.prefill_replicas)]
                + [(f"d{i}", "decode")
                   for i in range(args.decode_replicas)]
            )
        else:
            members = [(f"r{i}", "mixed") for i in range(args.fleet)]
        if args.model == "stub":
            def make_spec(name: str, role: str = "mixed") -> ReplicaSpec:
                return stub_spec(
                    name, delay_s=args.stub_delay,
                    num_pages=args.stub_pages,
                    page_size=args.stub_page_size, role=role,
                    max_batch=args.stub_max_batch,
                )
        else:
            chip_ids = itertools.count()
            child = [
                sys.executable, "-m",
                "triton_distributed_tpu.serving.run_server",
                "--model", args.model, "--port", "0", "--continuous",
                "--mode", args.mode, "--tp", str(args.tp),
                "--max-batch", str(args.max_batch),
                "--temperature", str(args.temperature),
            ]
            if args.kv_dtype:
                child += ["--kv-dtype", args.kv_dtype]
            if args.speculative:
                child += ["--speculative", str(args.speculative)]
            if args.ns != 8:
                child += ["--ns", str(args.ns)]
            if args.resident:
                child += ["--resident"]
            # --tier-dir promises a restart-safe fleet from one flag:
            # children must actually EXPORT snapshots for the
            # supervisor's resume store to hold anything (the
            # supervisor derives its pull cadence from resume_dir the
            # same way). An explicit --snapshot-every still wins.
            snap_every = args.snapshot_every or (8 if args.tier_dir else 0)
            if snap_every:
                child += ["--snapshot-every", str(snap_every)]
            if args.num_experts:
                child += ["--num-experts", str(args.num_experts)]
            if args.top_k:
                child += ["--top-k", str(args.top_k)]
            if args.moe_intermediate:
                child += ["--moe-intermediate", str(args.moe_intermediate)]
            for flag in ("num_layers", "first_k_dense", "experts_held",
                         "expert_offset", "vocab_rows"):
                if getattr(args, flag):
                    child += ["--" + flag.replace("_", "-"),
                              str(getattr(args, flag))]
            if args.tier_bytes:
                child += ["--tier-bytes", str(args.tier_bytes)]

            def make_spec(name: str, role: str = "mixed") -> ReplicaSpec:
                argv_i = list(child)
                # One chip per child. A TPU chip belongs to one process
                # at a time, so children that all see every chip
                # contend for chip 0 and all but one fail; this process
                # never touches JAX, and each tp=1 child is shown its
                # own chip (libtpu reads these; they mean nothing on
                # other platforms). A child past the host's chip count
                # fails at its own start-up with libtpu's reason.
                # tp > 1 children get no placement yet (ROADMAP R2).
                env_i = None
                if args.tp == 1:
                    env_i = {
                        "TPU_VISIBLE_CHIPS": str(next(chip_ids)),
                        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                        "TPU_PROCESS_BOUNDS": "1,1,1",
                    }
                if args.tier_dir:
                    # Default: per-child tier dirs — one disk tier per
                    # engine (digest-keyed entries would be content-
                    # identical across children, but per-child dirs
                    # keep snapshot buffers and byte accounting
                    # disjoint). --tier-shared mounts every child on
                    # the SAME dir instead (docs/scale-out.md "KV
                    # fabric"): atomic-rename writes and checksummed,
                    # digest-keyed entries make concurrent writers
                    # safe, and a fresh autoscaler replica's disk
                    # prescan finds the pool's spills at boot — the
                    # warm-boot path.
                    argv_i += [
                        "--tier-dir",
                        (args.tier_dir if args.tier_shared
                         else os.path.join(args.tier_dir, name)),
                    ]
                return ReplicaSpec(name, argv_i, env=env_i, role=role)

        specs = [make_spec(name, role) for name, role in members]
        launcher = None
        if args.hosts or args.fake_hosts:
            # Multi-host fleet (docs/scale-out.md "Multi-host fleet"):
            # spread the children round-robin across named failure
            # domains so losing a whole host is ONE host_down event
            # with parallel re-placement, not N independent timeouts.
            from triton_distributed_tpu.serving.launcher import (
                FakeHostLauncher,
                SSHLauncher,
            )

            if args.hosts:
                host_names = [h.strip() for h in args.hosts.split(",")
                              if h.strip()]
                if not host_names:
                    p.error("--hosts got no host names.")
                launcher = SSHLauncher(host_names)
            else:
                host_names = [f"h{i}" for i in range(args.fake_hosts)]
                launcher = FakeHostLauncher(host_names)
            for i, spec in enumerate(specs):
                spec.host = host_names[i % len(host_names)]
        sup = FleetSupervisor(
            specs, policy=policy, snapshot_s=args.snapshot_s,
            launcher=launcher,
            connect_timeout_s=args.connect_timeout,
            # --tier-dir makes the FLEET restart-safe too: pulled
            # snapshots persist under DIR/resume and a restarted
            # supervisor resumes re-submitted requests from them.
            resume_dir=(os.path.join(args.tier_dir, "resume")
                        if args.tier_dir else None),
            # Tiered real-model children carry a FabricClient; the
            # supervisor broadcasts the peer table so local misses can
            # fault back over the wire (docs/scale-out.md "KV fabric").
            tier_fabric=(args.model != "stub"
                         and bool(args.tier_bytes or args.tier_dir)),
            router_kw={
                "drain_grace_s": args.drain_grace,
                "request_timeout_s": args.request_timeout or None,
            },
        )
        router = sup.start()
        scaler = None
        if args.autoscale:
            from triton_distributed_tpu.serving.autoscaler import (
                Autoscaler,
            )

            scaler = Autoscaler(
                sup, lambda role, name: make_spec(name, role),
                pool_bounds={
                    "prefill": (args.prefill_replicas,
                                args.prefill_replicas + 2),
                    "decode": (args.decode_replicas,
                               args.decode_replicas + 2),
                },
                drain_grace_s=args.drain_grace,
            ).start()
        server = ModelServer(
            router, host=args.host, port=args.port,
            advertise_host=args.advertise_host,
            drain_grace_s=args.drain_grace, slo=slo,
        )
        shape = (f"{args.prefill_replicas}p+{args.decode_replicas}d"
                 if pool_fleet else f"x{args.fleet}")
        print(f"serving {args.model} fleet {shape} "
              f"({policy} router"
              f"{', autoscaled' if scaler is not None else ''}, "
              f"logs {sup.log_dir}) on "
              f"{server.host}:{server.port}")
        _write_port_file(args.port_file, server.advertise_host, server.port)
        try:
            server.serve_forever()
        finally:
            if scaler is not None:
                scaler.stop()
            sup.shutdown()
        return 0

    if args.model == "stub":
        # Process-fleet replica stub: the full wire server over the
        # deterministic control-plane engine — no mesh, no model load,
        # ~import-cost startup (models/stub.py).
        from triton_distributed_tpu.models.stub import StubEngine

        engine = StubEngine(
            num_pages=args.stub_pages, page_size=args.stub_page_size,
            delay_s=args.stub_delay, max_batch=args.stub_max_batch,
        )
        server = ModelServer(
            engine, host=args.host, port=args.port,
            advertise_host=args.advertise_host,
            drain_grace_s=args.drain_grace, slo=slo,
        )
        print(f"serving stub on {server.host}:{server.port}")
        _write_port_file(args.port_file, server.advertise_host, server.port)
        server.serve_forever()
        return 0

    from triton_distributed_tpu.models import AutoLLM
    from triton_distributed_tpu.models.engine import Engine
    from triton_distributed_tpu.runtime.compile_cache import (
        enable_compile_cache,
    )
    from triton_distributed_tpu.runtime.mesh import initialize_distributed

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    # On TPU each replica gets a device slice and a model of its own;
    # stacking replicas on one chip buys nothing and hides the others,
    # so more replicas than slices is refused. Off TPU (tests, CPU
    # demos) the replicas share one model on the first slice: the CPU
    # backend's devices are one host, and a second copy buys nothing.
    on_tpu = devices[0].platform == "tpu"
    n_slices = len(devices) // args.tp
    if on_tpu and args.replicas > n_slices:
        p.error(
            f"--replicas {args.replicas} with --tp {args.tp} needs "
            f"{args.replicas * args.tp} chips and this host has "
            f"{len(devices)}: replicas would share chips. Use at most "
            f"--replicas {n_slices}."
        )
    models = []
    for j in range(max(args.replicas, 1) if on_tpu else 1):
        # The LAST context built stays the process-global one; every
        # model carries its own, and all of them agree on the platform.
        ctx = initialize_distributed(
            tp=args.tp, devices=devices[j * args.tp: (j + 1) * args.tp]
        )
        models.append(
            AutoLLM.from_pretrained(model_name, ctx=ctx, **overrides)
        )
    model = models[0]
    # Say what the kernels run on: an interpreted run on a host that
    # lost its chip must not read like a chip run.
    pallas = ("compiled by Mosaic" if ctx.pallas_interpret() is False
              else "INTERPRETED")
    rung = f", mesh by {ctx.topology.mesh_rung}" if ctx.on_tpu else ""
    print(f"device: {devices[0].platform} {devices[0].device_kind!r} "
          f"x{len(devices)} (tp={args.tp}{rung}); Pallas kernels "
          f"{pallas}; compile cache {cache_dir}")
    # --trace: device-side kernel tracing rides the mega engines only
    # (the xla/pallas paths have no device ring); host profiling wraps
    # the run regardless of mode.
    kernel_trace = bool(args.trace) and args.mode == "mega"
    # The front door and each replica take as many payloads in flight as
    # there are decode slots, and never fewer than 8.
    max_pending = max(8, args.max_batch)
    if args.replicas > 0:
        from triton_distributed_tpu.models.continuous import ContinuousEngine
        from triton_distributed_tpu.serving.router import Router

        tiered = bool(args.tier_bytes or args.tier_dir)
        shared_tier = None
        if tiered and args.tier_shared:
            # One in-process PageStore behind every replica
            # (docs/scale-out.md "KV fabric"): each engine's spills
            # land where its siblings' fault-backs look, no fabric
            # round-trip needed. Owner-only deletes keep eviction safe.
            from triton_distributed_tpu.models.kv_tier import PageStore

            shared_tier = PageStore(
                capacity_bytes=args.tier_bytes or (64 << 20),
                dir=args.tier_dir, fsync=False,
            )
        engines = [
            ContinuousEngine(
                models[i % len(models)], max_batch=args.max_batch,
                mode=args.mode,
                temperature=args.temperature, prefix_cache=True,
                kv_dtype=args.kv_dtype, speculative=args.speculative,
                kernel_trace=kernel_trace,
                ns=args.ns, resident=args.resident,
                snapshot_every=args.snapshot_every,
                tier=shared_tier,
                tier_bytes=args.tier_bytes,
                tier_dir=(os.path.join(args.tier_dir, f"r{i}")
                          if args.tier_dir and shared_tier is None
                          else None),
            )
            for i in range(args.replicas)
        ]
        if tiered and shared_tier is None and len(engines) > 1:
            # Per-replica tiers → cross-wire the KV fabric in-process
            # (docs/scale-out.md "KV fabric"): each engine's local tier
            # miss probes its siblings' stores before re-prefilling.
            from triton_distributed_tpu.models.kv_tier import (
                FabricClient,
                LocalFabricPeer,
            )

            for i, eng in enumerate(engines):
                fc = FabricClient()
                fc.set_peers([
                    LocalFabricPeer(f"r{j}", other.tier)
                    for j, other in enumerate(engines)
                    if j != i and other.tier is not None
                ])
                eng.fabric = fc
        engine = Router(
            engines, policy=policy, drain_grace_s=args.drain_grace,
            request_timeout_s=args.request_timeout or None,
            replica_max_pending=max_pending,
        )
        what = f"{args.model} x{args.replicas} ({policy} router)"
    elif args.continuous:
        # The process-fleet child shape (docs/scale-out.md): ONE
        # ContinuousEngine speaking 'requests' payloads, with the
        # migration surface (export_slots/handoff verbs) live.
        from triton_distributed_tpu.models.continuous import ContinuousEngine

        fabric = None
        if args.tier_bytes or args.tier_dir:
            # Every tiered fleet child carries a FabricClient so the
            # supervisor's tier_peers broadcast has somewhere to land
            # (docs/scale-out.md "KV fabric"); peerless it is inert —
            # _tier_fill treats an empty peer table as fabric-off.
            from triton_distributed_tpu.models.kv_tier import FabricClient

            fabric = FabricClient()
        engine = ContinuousEngine(
            model, max_batch=args.max_batch, mode=args.mode,
            temperature=args.temperature, prefix_cache=True,
            kv_dtype=args.kv_dtype, speculative=args.speculative,
            kernel_trace=kernel_trace,
            ns=args.ns, resident=args.resident,
            snapshot_every=args.snapshot_every,
            tier_bytes=args.tier_bytes, tier_dir=args.tier_dir,
            fabric=fabric,
        )
        what = f"{args.model} (continuous, tp={args.tp})"
    else:
        engine = Engine(
            model, temperature=args.temperature, mode=args.mode,
            verbose=True,
            # Both knobs ride the paged engine (scales/verify chunks
            # live on the page pool).
            paged=bool(args.kv_dtype or args.speculative),
            kv_dtype=args.kv_dtype, speculative=args.speculative,
            kernel_trace=kernel_trace,
        )
        what = f"{args.model} (tp={args.tp})"
    server = ModelServer(
        engine, host=args.host, port=args.port, max_pending=max_pending,
        advertise_host=args.advertise_host,
        drain_grace_s=args.drain_grace, trace_dir=args.trace, slo=slo,
    )
    print(f"serving {what} on {server.host}:{server.port}")
    _write_port_file(args.port_file, server.advertise_host, server.port)
    if args.trace:
        # Host capture wraps the whole serving run; on exit the ranks'
        # chrome traces AND every traced mega launch's device task rows
        # merge into ONE timeline (docs/profiling.md).
        from triton_distributed_tpu.obs import kernel_trace as _kt
        from triton_distributed_tpu.runtime.profiling import group_profile

        with group_profile("serve", out_dir=args.trace, merge=False):
            server.serve_forever()
        launches = getattr(engine, "kernel_trace_launches", lambda: [])()
        merged = _kt.merge_with_host_profile("serve", args.trace, launches)
        print(f"merged trace: {merged} "
              f"({len(launches)} traced mega launches)")
    else:
        server.serve_forever()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
