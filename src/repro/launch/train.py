"""FL training driver, composed end-to-end from the ``repro.fl`` registry.

Two execution paths, one composition API:

* ``--engine mesh`` (default) — the gradient-level FedEntropy round
  (core/distributed.py): one jitted train step over the device mesh, the
  judge axis traced *inside* the step (``Judge.traced()``, optionally the
  Pallas sweep via ``--judge-backend pallas``), the selector feeding mesh
  client slots per round.
* ``--engine sequential | pipelined | async`` — the weights-level
  ``repro.fl`` server (paper Alg. 2 with E local epochs) over the same
  token corpus, built with ``fl.build(..., engine=...)``; ``pipelined``
  adds the runtime subsystem's mesh-sharded client fan-out and
  (``--speculate``) verdict speculation, ``async`` streams client updates
  under a simulated arrival clock (``--clock``) with per-arrival
  max-entropy admission, flushing every ``--buffer-size`` arrivals with
  ``--staleness-alpha`` damping.

Every axis — selector, judge, engine — resolves through ``repro.fl``
registries, so both paths run the identical composition code the
benchmarks and tests use. (At the gradient level, masked size-weighted
gradient averaging IS the weighted aggregator at E=1 — see
core/distributed.py's module docstring — which is why the mesh path has
no separate aggregator knob.)

CPU-friendly: ``--mesh host`` uses whatever devices exist; reduced configs
via ``--reduced``. Example:

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --reduced \
      --steps 20 --clients 8 --case case1 --mesh host
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --reduced \
      --engine pipelined --speculate --steps 10

One chip's share of an expert-parallel MoE (the held-expert layer: 1 of 2
shares of the reduced config's experts and vocabulary; at published widths
``--arch moonlight-16b-a3b --expert-parallel 8 --num-layers 6``):

  PYTHONPATH=src python -m repro.launch.train --arch moonlight-16b-a3b \
      --reduced --expert-parallel 2 --steps 3 --clients 4 --seq-len 32

LM quickstart (the scan engine at LM scale — eps-greedy pools folded on
device, O(cohort x vocab) stacked bytes per round via remat):

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --reduced \
      --engine scan --rounds-per-scan 4 --params-mode remat \
      --selector pools-traced --lm-objective window --steps 8
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import repro.fl as fl
from ..configs import ARCHS
from ..core.distributed import FedSpec, make_train_step
from ..data.synthetic import make_token_dataset
from ..fl.spans import route_counter
from ..kernels._platform import compiling_for
from ..optim import adamw, sgd
from ..checkpoint import save
from ..compile_cache import use_compile_cache
from ..models.api import build_model
from ..sharding.ctx import use_mesh
from .mesh import make_host_mesh


def build_fl_corpus(cfg, num_clients: int, case: str, seq_len: int,
                    seed: int = 0):
    """Domain-skewed token corpus partitioned into logical FL clients."""
    num_domains = max(4, num_clients // 2)
    x, dom = make_token_dataset(
        vocab_size=min(cfg.vocab_size, 2048),
        num_domains=num_domains,
        docs_per_domain=max(64, 8 * num_clients),
        seq_len=seq_len, seed=seed)
    rng = np.random.default_rng(seed)
    clients: list[np.ndarray] = []
    if case == "case1":          # one domain per client
        for i in range(num_clients):
            idx = np.where(dom == i % num_domains)[0]
            clients.append(rng.permutation(idx))
    elif case == "case2":        # two domains per client
        for i in range(num_clients):
            a, b = i % num_domains, (i + 1) % num_domains
            idx = np.where((dom == a) | (dom == b))[0]
            clients.append(rng.permutation(idx))
    else:                         # dirichlet over domains
        props = rng.dirichlet(np.full(num_domains, 0.3), size=num_clients)
        for i in range(num_clients):
            ds = rng.choice(num_domains, size=256, p=props[i])
            idx = np.concatenate([
                rng.choice(np.where(dom == d0)[0], 1) for d0 in ds])
            clients.append(idx)
    return x, clients


def _components(args, *, host_oracle: bool):
    """Resolve the selector and judge axes from the ``repro.fl`` registry.

    ``host_oracle=True`` (server engines) keeps the host-side judge on the
    float64 numpy oracle — the verdict of record, and the check that
    catches float32 tie-margin misspeculation; ``--judge-backend`` only
    picks the *traced* implementation (mesh step / pipelined speculation).
    """
    sel_cls = fl.get("selector", args.selector)
    config = fl.ServerConfig(num_clients=args.logical_clients,
                             participation=args.clients /
                             max(args.logical_clients, 1),
                             eps=args.eps, seed=args.seed,
                             group_size=args.group_size,
                             num_clusters=args.num_clusters)
    selector = sel_cls.from_config(config=config, local=None)
    if args.judge == "maxent":
        judge = fl.MaxEntropyJudge(
            backend="numpy" if host_oracle else args.judge_backend)
    else:
        judge = fl.get("judge", args.judge)()
    return config, selector, judge


def lm_window_apply(model, cfg):
    """Adapter: (params, x:(B, L+1) tokens) -> ((B, L, V) next-token
    logits for targets ``x[:, 1:]``, feats) — the full-window LM contract
    :class:`repro.fl.LMWindowStrategy` (``--lm-objective window``)
    consumes. Every position trains, not just the final token; the soft
    label becomes the weighted mean next-token distribution over all
    positions (paper Eq. 2, LM analog)."""
    def apply_fn(params, x):
        batch = {"tokens": x[:, :-1]}
        b = x.shape[0]
        if cfg.family == "vlm":
            batch["patches"] = jnp.zeros(
                (b, cfg.num_patches, cfg.d_model), jnp.float32)
        if cfg.family == "encdec":
            batch["frames"] = jnp.zeros(
                (b, cfg.encoder_seq, cfg.d_model), jnp.float32)
        logits, _, _ = model.forward(params, batch)
        logits = logits.astype(jnp.float32)
        return logits, logits[:, -1, :]
    return apply_fn


def lm_client_apply(model, cfg):
    """Adapter: (params, x:(B, L) tokens) -> (next-token logits, feats) so
    the weights-level ``Server``/``client_update`` machinery drives an LM.
    Each sample is an (L,) window; the classification target is its final
    token, the soft label (paper Eq. 2) the mean next-token distribution —
    the LM analog of the per-device label signature."""
    def apply_fn(params, x):
        batch = {"tokens": x[:, :-1]}
        b = x.shape[0]
        if cfg.family == "vlm":
            batch["patches"] = jnp.zeros(
                (b, cfg.num_patches, cfg.d_model), jnp.float32)
        if cfg.family == "encdec":
            batch["frames"] = jnp.zeros(
                (b, cfg.encoder_seq, cfg.d_model), jnp.float32)
        logits, _, _ = model.forward(params, batch)
        last = logits[:, -1, :].astype(jnp.float32)
        return last, last
    return apply_fn


def stack_lm_clients(corpus, client_idx, samples: int, seq_len: int,
                     seed: int):
    """(N, S, L+1) token windows + final-token labels for the fl server."""
    rng = np.random.default_rng(seed)
    xs, ys = [], []
    for rows in client_idx:
        take = rng.choice(rows, samples)
        win = corpus[take, : seq_len + 1]
        xs.append(win)
        ys.append(win[:, -1])
    return {
        "x": jnp.asarray(np.stack(xs), jnp.int32),
        "y": jnp.asarray(np.stack(ys), jnp.int32),
        "w": jnp.ones((len(client_idx), samples), jnp.float32),
    }


def build_drift_events(args, config, corpus, client_idx) -> list:
    """One label-drift event at ``--drift-at``: half the clients (seeded
    choice) re-sample their windows from their ring-neighbor's domain
    rows with a fresh draw stream — the LM analog of a label-distribution
    re-partition (see ``repro.data.partition.drift_schedule``)."""
    n = config.num_clients
    rng = np.random.default_rng(args.seed)
    k = max(1, n // 2)
    drifting = sorted(int(c) for c in
                      rng.choice(n, size=k, replace=False))
    rotated = [client_idx[(c + 1) % n] for c in drifting]
    new = stack_lm_clients(corpus, rotated, args.samples_per_client,
                           args.seq_len, args.seed + 1)
    return [fl.DriftEvent(
        round=args.drift_at, clients=tuple(drifting),
        data={key: np.asarray(v) for key, v in new.items()})]


def run_server_engine(args, cfg, model, corpus, client_idx) -> None:
    """Weights-level rounds through ``fl.build`` (sequential or pipelined)."""
    config, selector, judge = _components(args, host_oracle=True)
    data = stack_lm_clients(corpus, client_idx, args.samples_per_client,
                            args.seq_len, args.seed)
    drift = (build_drift_events(args, config, corpus, client_idx)
             if args.drift_at >= 0 else None)
    if args.engine == "async":
        if args.speculate:
            raise SystemExit(
                "--speculate is a pipelined-engine knob: the async engine "
                "has no round barrier to overlap the oracle with")
        runtime = fl.AsyncConfig(
            buffer_size=args.buffer_size,
            staleness_alpha=args.staleness_alpha,
            clock=args.clock, seed=args.seed)
    elif args.engine == "scan":
        if args.speculate:
            raise SystemExit(
                "--speculate is a pipelined-engine knob: the scan engine "
                "speculates every in-scan verdict already (the float64 "
                "oracle replays each R-round block)")
        runtime = fl.ScanConfig(rounds_per_scan=args.rounds_per_scan,
                                spec_backend=args.judge_backend,
                                params_mode=args.params_mode)
    else:
        runtime = fl.RuntimeConfig(speculate=args.speculate,
                                   spec_backend=args.judge_backend)
    if args.method:
        # named composition (e.g. fedcat): its own selector/judge axes
        # resolve from the registry via config (--group-size sizes chains);
        # refuse explicit axis flags rather than silently dropping them
        if args.selector != "pools" or args.judge != "maxent":
            raise SystemExit(
                f"--method {args.method} names a full composition; drop "
                "--selector/--judge (compose axes via the legacy flags "
                "without --method instead)")
        composition, selector, judge = args.method, None, None
    else:
        composition = "fedavg" if args.no_fedentropy else "fedentropy"
        if args.no_fedentropy:
            judge = None
    window = args.lm_objective == "window"
    if window and args.method:
        raise SystemExit(
            f"--lm-objective window swaps the client strategy for lmstep; "
            f"--method {args.method} composes its own strategy axis — "
            "drop one of the two")
    if args.num_clusters > 1 and window:
        raise SystemExit(
            "--num-clusters > 1 runs the plain vmapped ClientUpdate "
            "(per-client bank centers); --lm-objective window swaps in "
            "the lmstep strategy's own client fn — drop one of the two")
    apply_fn = (lm_window_apply if window else lm_client_apply)(model, cfg)
    server = fl.build(
        composition, apply_fn, model.init(
            jax.random.PRNGKey(args.seed)), data, config,
        fl.LocalSpec(epochs=args.local_epochs, lr=args.lr,
                     batch_size=args.per_client_batch),
        selector=selector, strategy="lmstep" if window else None,
        judge=judge,
        # the cluster axis: --num-clusters>1 opts any composition into the
        # K-center bank with the --cluster-assign assigner; K=1 leaves a
        # named clustered composition (e.g. --method ifca) on its own
        # recipe, which then reduces to the single-model path exactly
        cluster=args.cluster_assign if args.num_clusters > 1 else None,
        drift=drift,
        engine=args.engine, runtime=runtime, data_plane=args.data_plane)
    if args.dryrun:
        rep = server.corpus.memory_report()
        m = max(1, int(round(config.num_clients * config.participation)))
        print(f"dryrun: engine={args.engine} data_plane={rep['plane']}")
        print(f"  host-mapped bytes:     {rep['host_mapped_bytes']}"
              f" (mmap={rep['host_is_mmap']})")
        print(f"  device-resident bytes: {rep['device_resident_bytes']}")
        print(f"  staging bytes:         {rep['staging_nbytes']}")
        print(f"  clients: N={rep['num_clients']} cohort |S_t|={m} "
              f"(~{server.corpus.cohort_nbytes(m)}B/round host-slice "
              "equivalent)")
        return
    t0 = time.time()
    for it in range(args.steps):
        rec = server.round()
        extra = ""
        if "spec_hit" in rec:
            extra = (f" spec={'hit' if rec['spec_hit'] else 'miss'}"
                     f"{' redispatched' if rec['redispatched'] else ''}")
        if "staleness" in rec:
            extra = (f" t={rec['flush_time']:.2f}"
                     f" stale_max={max(rec['staleness'])}"
                     f" buf={rec['buffer_occupancy']}")
        if "cluster" in rec:
            occ = np.bincount(np.asarray(rec["cluster"]),
                              minlength=args.num_clusters)
            extra += f" clusters={'/'.join(str(int(c)) for c in occ)}"
        if "drift" in rec:
            extra += f" drift={sum(len(c) for c in rec['drift'])}cl"
        print(f"round {it:4d} pos={len(rec['positive'])}/"
              f"{len(rec['selected'])} ent={rec['entropy']:.4f}"
              f" comm={rec['comm']['total_bytes']}B{extra}", flush=True)
    dt = time.time() - t0
    # read stats off the SERVER's selector: a speculative hit adopts a
    # deepcopy, orphaning the local reference built above
    stats = server.selector.stats()
    print(f"done: {args.steps} rounds in {dt:.1f}s "
          f"({dt / args.steps:.2f}s/round); selector={stats}")
    if args.ckpt_dir:
        path = save(args.ckpt_dir, args.steps, server.global_params,
                    meta={"arch": cfg.name, "engine": args.engine,
                          "selector": stats})
        print("checkpoint:", path)


def build_mesh_step(args, model, judge):
    """The mesh engine's jitted train step (params and optimizer state
    donated) and its optimizer."""
    fed = FedSpec(num_clients=args.clients, enabled=not args.no_fedentropy)
    opt = (sgd(lr=args.lr, momentum=0.5) if args.optimizer == "sgd"
           else adamw(lr=args.lr))
    step = make_train_step(model, opt, fed, judge_fn=judge.traced())
    return jax.jit(step, donate_argnums=(0, 1)), opt


def mesh_step_memory(argv: list[str], mesh) -> dict:
    """``memory_analysis()`` of the mesh train step for ``argv`` (train's
    flags), compiled for ``mesh`` from abstract shapes: argument, output,
    alias, temporary and generated-code bytes, and ``total`` = argument
    + output - alias + temporary, the device memory the step needs.
    Kernels take the choices of the mesh's platform (a described TPU
    compiles the Pallas kernels it would run)."""
    args = parse_args(list(argv))
    _, model = build_lm(args)
    _, _, judge = _components(args, host_oracle=False)
    jitted, opt = build_mesh_step(args, model, judge)
    replicated = NamedSharding(mesh, P())

    def abstract(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=replicated), tree)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt_state = abstract(jax.eval_shape(opt.init, params))
    tokens = jax.ShapeDtypeStruct(
        (args.clients * args.per_client_batch, args.seq_len + 1),
        jnp.int32, sharding=replicated)
    with compiling_for(mesh.devices.flat[0].platform), mesh, \
            use_mesh(mesh):
        compiled = jitted.lower(abstract(params), opt_state,
                                {"tokens": tokens}).compile()
    ma = compiled.memory_analysis()
    out = {k: int(getattr(ma, f"{k}_size_in_bytes")) for k in
           ("argument", "output", "alias", "temp", "generated_code")}
    out["total"] = (out["argument"] + out["output"] - out["alias"]
                    + out["temp"])
    return out


def run_mesh_engine(args, cfg, model, corpus, client_idx) -> list[dict]:
    """Gradient-level rounds: one jitted mesh step, judge traced inside.
    Returns each step's scalar metrics."""
    _, selector, judge = _components(args, host_oracle=False)
    mesh = make_host_mesh()
    m = args.clients
    bsz = m * args.per_client_batch
    jitted, opt = build_mesh_step(args, model, judge)

    key = jax.random.PRNGKey(args.seed)
    params = model.init(key)
    opt_state = opt.init(params)
    rng = np.random.default_rng(args.seed)

    records = []
    t0 = time.time()
    with mesh, use_mesh(mesh):
        for it in range(args.steps):
            sel = selector.select(m)                    # logical clients
            rows = []
            for c in sel:
                take = rng.choice(client_idx[c], args.per_client_batch)
                rows.append(corpus[take, : args.seq_len + 1])
            tokens = jnp.asarray(np.concatenate(rows), jnp.int32)
            extra = {}
            if cfg.family == "vlm":
                extra["patches"] = jnp.zeros(
                    (bsz, cfg.num_patches, cfg.d_model), jnp.float32)
            if cfg.family == "encdec":
                extra["frames"] = jnp.zeros(
                    (bsz, cfg.encoder_seq, cfg.d_model), jnp.float32)
            params, opt_state, metrics = jitted(
                params, opt_state, {"tokens": tokens, **extra})
            mask = np.asarray(metrics["mask"])
            pos = [sel[i] for i in range(m) if mask[i] > 0]
            neg = [sel[i] for i in range(m) if mask[i] == 0]
            selector.update(pos, neg)
            rec = {k: float(metrics[k]) for k in
                   ("loss", "num_positive", "entropy", "grad_norm")}
            if "expert_rows" in metrics:
                route_counter(metrics["expert_rows"])
            records.append(rec)
            print(f"step {it:4d} loss={rec['loss']:.4f} "
                  f"pos={int(rec['num_positive'])}/{m} "
                  f"ent={rec['entropy']:.4f} "
                  f"gnorm={rec['grad_norm']:.3f}", flush=True)
    dt = time.time() - t0
    print(f"done: {args.steps} rounds in {dt:.1f}s "
          f"({dt / args.steps:.2f}s/round); selector={selector.stats()}")
    if args.ckpt_dir:
        path = save(args.ckpt_dir, args.steps, params,
                    meta={"arch": cfg.name, "selector": selector.stats()})
        print("checkpoint:", path)
    return records


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    """The driver's flags, parsed from ``argv`` (default
    ``sys.argv[1:]``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--num-layers", type=int, default=0,
                    help="run the first N layers only: one pipeline "
                         "stage's depth (0 = all)")
    ap.add_argument("--expert-parallel", type=int, default=1,
                    help="run this chip's share of an N-chip expert-"
                         "parallel group (rank 0): num_experts/N experts "
                         "of each expert layer and vocab_size/N rows of "
                         "the vocabulary; routing stays over every "
                         "expert")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--clients", type=int, default=8,
                    help="client slots per round (M = |S_t|)")
    ap.add_argument("--logical-clients", type=int, default=32,
                    help="logical FL population feeding the slots")
    ap.add_argument("--case", default="case1",
                    choices=["case1", "case2", "case3"])
    ap.add_argument("--per-client-batch", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--optimizer", default="sgd", choices=["sgd", "adamw"])
    ap.add_argument("--no-fedentropy", action="store_true")
    ap.add_argument("--method", default="",
                    choices=["", "fedentropy", "fedavg", "fedcat",
                             "fedcat+maxent", "fedentropy+queue", "ifca",
                             "ifca+maxent", "fesem"],
                    help="named repro.fl composition (server engines); "
                         "fedcat chains grouped devices sequentially, "
                         "fedcat+maxent filters chains with judgment, "
                         "fedentropy+queue ranks clients by corpus "
                         "entropy with a dynamic data queue; ifca/"
                         "ifca+maxent/fesem run the K-center clustered "
                         "ModelBank (size via --num-clusters)")
    ap.add_argument("--num-clusters", type=int, default=1,
                    help="K ModelBank centers (server engines); 1 keeps "
                         "the single global model, >1 clusters clients "
                         "via --cluster-assign with per-cluster judgment "
                         "and aggregation")
    ap.add_argument("--cluster-assign", default="ifca",
                    choices=["ifca", "fesem"],
                    help="cluster assigner when --num-clusters > 1: ifca "
                         "= per-round loss argmin over the centers, "
                         "fesem = sticky weight-distance re-filing")
    ap.add_argument("--drift-at", type=int, default=-1,
                    help="re-partition half the clients' local data at "
                         "this round (label drift; server engines); -1 "
                         "disables")
    ap.add_argument("--group-size", type=int, default=2,
                    help="FedCAT chain length (fedcat compositions)")
    ap.add_argument("--engine", default="mesh",
                    choices=["mesh", "sequential", "pipelined", "async",
                             "scan"],
                    help="mesh = gradient-level jitted step; sequential/"
                         "pipelined/async/scan = weights-level repro.fl "
                         "engines (async streams arrivals through "
                         "max-entropy admission; scan folds R rounds "
                         "into one lax.scan program)")
    ap.add_argument("--rounds-per-scan", type=int, default=4,
                    help="scan engine: rounds folded per jitted scan "
                         "block (needs --selector uniform or "
                         "pools-traced to fold >1)")
    ap.add_argument("--params-mode", default="stack",
                    choices=["stack", "remat"],
                    help="scan engine rewind points: stack keeps R "
                         "post-round param copies in the scan's ys, "
                         "remat re-runs confirmed rounds on a mismatch "
                         "— O(cohort*vocab) stacked bytes per round, "
                         "the LM-scale mode")
    ap.add_argument("--lm-objective", default="last-token",
                    choices=["last-token", "window"],
                    help="server engines: last-token treats each window "
                         "as a classification sample (final token is "
                         "the label); window trains every next-token "
                         "position via the lmstep strategy (the LM "
                         "fine-tune objective)")
    ap.add_argument("--buffer-size", type=int, default=0,
                    help="async engine: screened arrivals per flush "
                         "(0 = cohort size, the reduction case)")
    ap.add_argument("--staleness-alpha", type=float, default=0.0,
                    help="async engine: (1+tau)^-alpha damping of "
                         "admitted updates (0 = off)")
    ap.add_argument("--clock", default="zero",
                    choices=["zero", "uniform", "straggler"],
                    help="async engine: simulated per-client arrival "
                         "latency model (seeded, virtual time)")
    ap.add_argument("--selector", default="pools",
                    choices=["pools", "pools-traced", "uniform", "queue"],
                    help="repro.fl Selector driving client admission "
                         "(pools-traced = the paper's eps-greedy pools "
                         "on a jax.random stream, scan-foldable; queue "
                         "= entropy-ranked dynamic data queues, stats "
                         "bound from the server's ClientCorpus)")
    ap.add_argument("--judge", default="maxent", choices=["maxent", "none"],
                    help="repro.fl Judge axis (both engines)")
    ap.add_argument("--judge-backend", default="xla",
                    choices=["xla", "pallas"],
                    help="traced judge implementation (mesh step / "
                         "pipelined speculation)")
    ap.add_argument("--speculate", action="store_true",
                    help="pipelined engine: overlap oracle judgment with "
                         "the next round's client compute")
    ap.add_argument("--data-plane", default="auto",
                    choices=["resident", "streaming", "auto"],
                    help="server engines: where client data lives — "
                         "resident stacks all N clients on device, "
                         "streaming keeps them host-side and uploads "
                         "only the cohort (prefetched under --speculate),"
                         " auto picks resident while N fits")
    ap.add_argument("--dryrun", action="store_true",
                    help="server engines: build the server, print the "
                         "data-plane memory report, and exit without "
                         "training")
    ap.add_argument("--local-epochs", type=int, default=1,
                    help="E local epochs (server engines)")
    ap.add_argument("--samples-per-client", type=int, default=16,
                    help="local dataset size per client (server engines)")
    ap.add_argument("--eps", type=float, default=0.8)
    ap.add_argument("--mesh", default="host")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def build_lm(args):
    """(config, model) for ``--arch``: f32 params and activations, no
    remat; ``--reduced`` shrinks the widths, ``--num-layers`` and
    ``--expert-parallel`` cut the model to one chip's share."""
    cfg = ARCHS[args.arch]
    if args.reduced:
        cfg = cfg.reduced()
    if args.num_layers:
        cfg = cfg.replace(num_layers=args.num_layers)
    ep = args.expert_parallel
    if ep > 1:
        if not cfg.experts_held or cfg.num_experts % ep or \
                cfg.vocab_size % ep:
            raise SystemExit(
                f"--expert-parallel {ep} needs a held-expert layer whose "
                f"experts ({cfg.num_experts}) and vocabulary "
                f"({cfg.vocab_size}) it divides")
        cfg = cfg.replace(experts_held=cfg.num_experts // ep,
                          vocab_size=cfg.vocab_size // ep)
    cfg = cfg.replace(remat="none", param_dtype="float32", dtype="float32")
    return cfg, build_model(cfg)


def main(argv: list[str] | None = None) -> list[dict] | None:
    """Parse ``argv`` (default ``sys.argv[1:]``) and train; returns the
    mesh engine's per-step metrics (``None`` for the server engines)."""
    args = parse_args(argv)
    use_compile_cache()
    cfg, model = build_lm(args)

    corpus, client_idx = build_fl_corpus(
        cfg, args.logical_clients, args.case, args.seq_len, args.seed)
    if args.engine == "mesh":
        if args.data_plane != "auto" or args.dryrun:
            # the mesh engine feeds token batches straight into the jitted
            # step — there is no corpus object to place on a plane or to
            # report memory for
            raise SystemExit(
                "--data-plane/--dryrun need a weights-level engine: use "
                "--engine sequential, pipelined, or async (the server "
                "owns the data-plane corpus)")
        if args.selector == "queue":
            # the mesh engine has no ClientCorpus to bind entropy stats or
            # data-queue schedules to — it would silently run uniform
            raise SystemExit(
                "--selector queue needs a weights-level engine: use "
                "--engine sequential or pipelined (the server binds the "
                "corpus stats the queue selector ranks on)")
        if args.method:
            # the gradient-level step has no composition axis to honor a
            # named recipe (fedcat chains thread whole models); refusing
            # beats silently running the default fedentropy path
            raise SystemExit(
                f"--method {args.method} needs a weights-level engine: "
                "use --engine sequential or pipelined (the mesh engine "
                "is composed via --no-fedentropy/--selector/--judge)")
        if args.num_clusters > 1 or args.drift_at >= 0:
            # the mesh step threads ONE replicated model through the jitted
            # program and owns no corpus object to re-partition mid-run
            raise SystemExit(
                "--num-clusters/--drift-at need a weights-level engine: "
                "use --engine sequential or pipelined (the server carries "
                "the ModelBank and applies the drift schedule)")
        return run_mesh_engine(args, cfg, model, corpus, client_idx)
    run_server_engine(args, cfg, model, corpus, client_idx)
    return None


if __name__ == "__main__":
    main()
