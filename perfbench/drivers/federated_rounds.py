"""Back-to-back federated rounds: the driver of every training mix.

Set-up builds one trainer from the deployment file and the seed,
replaces its weights with ones the benchmark made, seeds the embedding
server (``pretrain_round``) and runs one whole round through
``run_round``, recording on the way what the check needs: every
client's minibatches as its sampler hands them out, the first steps'
losses and optimizer state, the rows each client pulls before and after
the round, the averaged model and the round's accuracy.  The window
then drives the same trainer's ``run_round`` until the next round would
not fit, and counts the programs compiled in it (``window_compiles`` on
stderr).  After the window the plain reference (``yardstick/
reference.py``) recomputes the whole set-up round from the seed.

Nothing here depends on the architecture: the configuration's ``conv``
names its model module (``yardstick/models/<conv>.py``), which gives the
trainer's model arguments, the widths, the weights, the program's
parameter layout, the forward pass, the loss and the FLOPs.  Each
layer's weights travel as one tuple of leaves.
"""

from __future__ import annotations

import dataclasses
import gc
import sys
import time

import numpy as np

from perfbench import harness
from perfbench.yardstick import compare, flops, graphgen, reference

#: program spans inside ``run_round`` (``obsv/trace.py`` names)
ROUND_SPANS = ("client.pull", "client.train_epoch", "client.push_compute",
               "round.aggregate")


def _host_batch(mb) -> dict:
    """A sampler's minibatch as the plain arrays the check reads."""
    return {"blocks": [{k: np.asarray(getattr(b, k)) for k in
                        ("edge_src", "edge_dst", "edge_mask", "dst_mask")}
                       for b in mb.blocks],
            "input_ids": np.asarray(mb.input_ids),
            "seeds": np.asarray(mb.seeds),
            "seed_mask": np.asarray(mb.seed_mask)}


class RoundRecorder:
    """Records the set-up round.  Each client's sampler hands out its
    epochs through :meth:`_epochs`, which keeps every minibatch and
    marks the client whose steps follow.  The first-step numbers the
    check compares (each step's loss, the optimizer's state after one
    step) exist only at the step call, so the trainer's step is wrapped
    too; it reads only the step's outputs (params, optimizer state,
    loss) and every call goes to the real step."""

    def __init__(self, trainer, steps: int):
        self.trainer = trainer
        self.steps = steps
        self.epochs: dict[int, list[list[dict]]] = {}
        self.first: dict[int, list[dict]] = {}
        self.client = None

    def _epochs(self, ci, real):
        def epoch(*args, **kwargs):
            self.client = ci
            got = []
            self.epochs.setdefault(ci, []).append(got)
            for mb in real(*args, **kwargs):
                got.append(_host_batch(mb))
                yield mb
        return epoch

    def _step(self, *args, **kwargs):
        import jax
        out = self.inner(*args, **kwargs)
        recs = self.first.setdefault(self.client, [])
        if len(recs) < self.steps:
            rec = {"loss": float(out[2])}
            if not recs:
                rec["mu"] = jax.device_get(out[1].mu)
            if len(recs) + 1 == self.steps:
                rec["params"] = jax.device_get(out[0])
            recs.append(rec)
        return out

    def run_round(self, round_idx: int):
        tr = self.trainer
        self.inner = tr._train_step
        for ci, s in enumerate(tr.samplers):
            s.epoch = self._epochs(ci, s.epoch)
        tr._train_step = self._step
        try:
            return tr.run_round(round_idx, 0.0)
        finally:
            tr._train_step = self.inner
            for s in tr.samplers:
                del s.epoch


def _model(cfg: dict):
    return harness.model_of(cfg["conv"])


def _host(leaves) -> list[tuple]:
    """Per-layer tuples of leaves as host arrays."""
    return [tuple(np.asarray(a) for a in layer) for layer in leaves]


def precision(cfg: dict):
    """The matrix-product precision the configuration states, for the
    program's set-up and window (JAX's own option)."""
    import jax
    return jax.default_matmul_precision(cfg["matmul_precision"])


def _pulled(tr) -> dict:
    """Rows each client pulls from the embedding server now."""
    return {ci: [np.asarray(v) for v in
                 tr.ex_clients[ci].peek(tr.shards[ci].pull_nodes)]
            for ci in range(tr.k)}


def build(cfg: dict, seed: int):
    """Graph, weights and trainer of one run, driven through the set-up
    round, under the configuration's precision.  Returns ``(trainer,
    seen, graph, params0)``: ``seen`` is what the set-up round showed."""
    with precision(cfg):
        return _build(cfg, seed)


def _build(cfg: dict, seed: int):
    model = _model(cfg)
    tr, graph = trainer(cfg, seed)
    params0 = model.init(reference.key_from_seed(seed, 2), cfg)
    tr.params = model.to_program(params0)
    tr.pretrain_round()
    rec = RoundRecorder(tr, int(cfg["checked_steps"]))
    pulled_before = _pulled(tr)
    stats = rec.run_round(0)
    seen = {"epochs": rec.epochs, "first": rec.first,
            "pulled_before": pulled_before, "pulled_after": _pulled(tr),
            "avg": _host(model.from_program(tr.params)),
            "acc": float(stats.accuracy), "struct": structure_of(tr)}
    return tr, seen, graph, _host(params0)


def trainer(cfg: dict, seed: int):
    """The deployment's graph, its features drawn from the seed, and the
    program's trainer over it: the model module's ``program_args`` set
    the model's shape, the deployment's arguments are set here.
    Returns ``(trainer, graph)``."""
    from repro.core import FederatedGNNTrainer, default_strategies
    from repro.graphs.graph import Graph

    st = graphgen.structure(cfg)
    feats = graphgen.features(cfg, st["labels"], seed)
    g = Graph(indptr=st["indptr"], indices=st["indices"], features=feats,
              labels=st["labels"], train_mask=st["train_mask"],
              num_classes=int(cfg["classes"]), name=cfg["name"])
    strategy = dataclasses.replace(
        default_strategies(retention=int(cfg["retention"]))[cfg["strategy"]],
        codec=cfg["codec"], error_feedback=bool(cfg["error_feedback"]))
    tr = FederatedGNNTrainer(
        g, int(cfg["clients"]), strategy, **_model(cfg).program_args(cfg),
        fanout=int(cfg["fanout"]), batch_size=int(cfg["batch"]),
        epochs_per_round=int(cfg["epochs"]), lr=float(cfg["lr"]),
        seed=int(cfg["trainer_seed"]),
        eval_max_edges=int(cfg["eval_max_edges"]))
    graph = {"indptr": st["indptr"], "indices": st["indices"],
             "labels": st["labels"], "train_mask": st["train_mask"],
             "features": feats}
    return tr, graph


def counts(tr, cfg: dict) -> dict:
    """Work per round, from the trainer's shards: steps, model FLOPs
    and int8 wire bytes (each exchanged layer's rows at its width)."""
    model = _model(cfg)
    steps = flops_total = rows = 0
    for ci in range(tr.k):
        sh = tr.shards[ci]
        n_batches = -(-len(sh.train_vertices()) // int(cfg["batch"]))
        steps += int(cfg["epochs"]) * n_batches
        flops_total += int(cfg["epochs"]) * n_batches * \
            model.step_flops(cfg, len(sh.global_ids))
        rows += len(sh.pull_nodes) + len(sh.push_nodes)
    return {"steps_per_round": steps, "flops_per_round": flops_total,
            "codec_bytes_per_round": sum(
                flops.int8_codec_bytes(rows, w)
                for w in model.exchanged_widths(cfg))}


def structure_of(tr) -> dict:
    """The program's choices the check holds to the rules: the partition
    and, per client, its vertex numbering, pulled vertices and the
    in-edges its shard retained (global ids)."""
    out = {"part": np.asarray(tr.part), "global_ids": [], "pull_nodes": [],
           "shard_edges": []}
    for sh in tr.shards:
        gids = np.asarray(sh.global_ids)
        dst = np.repeat(np.arange(sh.num_local), np.diff(sh.indptr))
        out["global_ids"].append(gids)
        out["pull_nodes"].append(np.asarray(sh.pull_nodes))
        out["shard_edges"].append((gids[np.asarray(sh.indices)], gids[dst]))
    return out


def program_side(seen: dict, cfg: dict) -> dict:
    """The program's numbers: per client its first steps' losses, first
    gradient (Adam's first moment after one step over 1 - b1), weights
    after the last checked step and the rows it pulled for the round;
    then the rows pulled after the round, the averaged model and the
    round's accuracy."""
    model = _model(cfg)
    b1 = float(cfg["adam"]["b1"])
    first = {}
    for ci, recs in seen["first"].items():
        grad = [tuple(np.asarray(m) / (1 - b1) for m in layer)
                for layer in model.from_program(recs[0]["mu"])]
        params = _host(model.from_program(recs[-1]["params"]))
        first[ci] = {"loss": [r["loss"] for r in recs], "grad": grad,
                     "params": params, "cache": seen["pulled_before"][ci]}
    return {"first": first, "round": {"pulled": seen["pulled_after"],
                                      "avg": seen["avg"],
                                      "acc": seen["acc"]}}


def _same(x):
    return x


def reference_side(seen: dict, cfg: dict, graph: dict, params0, *,
                   mode: str = "highest", plant: dict | None = None) -> dict:
    """The reference (``mode="highest"``) or the control (``"high"``)
    over the set-up round's recorded choices, in the shape of
    :func:`program_side`.  ``plant`` may replace a stage's result to put
    a fault in the program's place (``perfbench/calibrate.py``): keys
    ``tables`` (the rows pulled for the round), ``stacked`` (a client's
    packed steps), ``residual`` (the error-feedback residuals),
    ``server`` (called with the rows after and before the round),
    ``average`` (called with the clients' models and weights) and
    ``eval`` (called with the averaged and the starting model).  Raises
    ``ValueError`` where a choice breaks the rules."""
    import jax.numpy as jnp
    model = _model(cfg)
    plant = plant or {}
    struct = seen["struct"]
    part = struct["part"]
    k = int(cfg["clients"])
    gi = reference.GraphIndex(graph["indptr"], graph["indices"], part, k)
    feats = jnp.asarray(graph["features"])
    rt = reference.int8_roundtrip
    h_pre = [np.asarray(h) for h in
             reference.pretrain_h(model.propagate, params0,
                                  graph["features"], gi, mode=mode)]
    seen_rows, have = _pulled_table(seen["pulled_before"], struct, h_pre)
    srv_pre = [reference.take_ties(h, rt(h), s, have)
               for h, s in zip(h_pre, seen_rows)]
    before = [rt(s) for s in srv_pre]
    tables = plant.get("tables", _same)(before)
    residual = plant.get("residual", _same)(
        [h - s for h, s in zip(h_pre, srv_pre)])
    tables = [jnp.asarray(t) for t in tables]
    adam = cfg["adam"]
    after = [np.zeros(t.shape, np.float32) for t in tables]
    first, finals, weights = {}, [], []
    for ci in range(k):
        e_src, e_dst = struct["shard_edges"][ci]
        reference.check_shard(gi, e_src, e_dst, client=ci,
                              retention=int(cfg["retention"]))
        train = np.nonzero((part == ci) & graph["train_mask"])[0]
        epochs = seen["epochs"].get(ci, [])
        if len(epochs) != int(cfg["epochs"]):
            raise ValueError(f"client {ci} sampled {len(epochs)} epochs")
        packed = []
        for ep in epochs:
            seeds = []
            for b in ep:
                layers, s = reference.block_to_edges(
                    b, struct["global_ids"][ci], client=ci, gi=gi,
                    fanout=int(cfg["fanout"]),
                    retention=int(cfg["retention"]),
                    train_mask=graph["train_mask"])
                seeds.append(s)
                packed.append(reference.pack_batch(
                    layers, s, labels=graph["labels"], part=part, client=ci))
            seeds = np.concatenate(seeds)
            if len(seeds) != len(train) or not np.array_equal(
                    np.sort(seeds), train):
                raise ValueError(f"client {ci}: an epoch's seeds are not "
                                 "its training vertices, each once")
        per_epoch = len(epochs[0])
        stacked = plant.get("stacked", _same)(
            reference.stack_batches(packed))
        losses, grads, ps = reference.local_round(
            [tuple(jnp.asarray(a) for a in layer) for layer in params0],
            stacked, feats, tables, loss=model.loss, mode=mode,
            lr=float(cfg["lr"]), b1=float(adam["b1"]), b2=float(adam["b2"]),
            eps=float(adam["eps"]))
        n = int(cfg["checked_steps"])
        first[ci] = {
            "loss": [float(x) for x in np.asarray(losses[:n])],
            "grad": _host(_at(grads, 0)),
            "params": _host(_at(ps, n - 1)),
            "cache": [np.asarray(t)[struct["pull_nodes"][ci]]
                      for t in tables]}
        at = int(cfg["push_after_epoch"]) * per_epoch - 1
        h = reference.client_h(model.propagate, _at(ps, at), feats, gi,
                               e_src, e_dst, tables, client=ci, mode=mode)
        mine = (part == ci)[:, None]
        after = [np.where(mine, np.asarray(x), a) for x, a in zip(h, after)]
        finals.append(_at(ps, -1))
        weights.append(float(len(train)))
    server = plant.get("server", lambda a, b: a)(
        [rt(a + r) for a, r in zip(after, residual)], srv_pre)
    pulled = [rt(s) for s in server]
    avg = plant.get("average", _average)(finals, weights)
    avg = _host(avg)
    sel = reference.eval_vertices(graph["indptr"], int(cfg["eval_max_edges"]),
                                  int(cfg["trainer_seed"]))
    acc = reference.accuracy(
        model.propagate, plant.get("eval", lambda a, p0: a)(avg, params0),
        graph["features"], graph["labels"], graph["train_mask"], gi, sel,
        mode=mode)
    return {"first": first,
            "round": {"pulled": {ci: [t[struct["pull_nodes"][ci]]
                                      for t in pulled] for ci in range(k)},
                      "before": {ci: [t[struct["pull_nodes"][ci]]
                                      for t in before] for ci in range(k)},
                      "avg": avg, "acc": acc}}


def _at(stepped, i):
    """Each leaf of per-step stacked leaves at step ``i``."""
    return [tuple(a[i] for a in layer) for layer in stepped]


def _pulled_table(pulled: dict, struct: dict, like) -> tuple[list, np.ndarray]:
    """The rows the clients pulled, as (V, H) tables, and which vertices
    were pulled."""
    have = np.zeros(len(struct["part"]), bool)
    tables = [np.zeros(np.shape(t), np.float32) for t in like]
    for ci, rows in pulled.items():
        gids = struct["pull_nodes"][ci]
        have[gids] = True
        for t, r in zip(tables, rows):
            t[gids] = np.asarray(r)[: len(gids)]
    return tables, have


def _average(models, weights):
    """FedAvg: the clients' models weighted by their training vertices."""
    total = sum(weights)
    return [tuple(sum(w * m[l][i] for w, m in zip(weights, models)) / total
                  for i in range(len(models[0][l])))
            for l in range(len(models[0]))]


def gaps_of(side: dict, ref: dict, params0) -> dict:
    per = [compare.client_gaps(side["first"][ci], ref["first"][ci], params0)
           for ci in sorted(ref["first"])]
    keep = [any(k) for k in zip(*(compare.moving_leaves(f["grad"])
                                  for f in ref["first"].values()))]
    return {**compare.worst(per),
            **compare.round_gaps(side["round"], ref["round"], params0, keep)}


def check(seen, cfg, graph, params0, limits) -> tuple[bool, dict]:
    """``correct`` and the compared numbers beside their limits."""
    got = {c: len(r) for c, r in seen["first"].items()}
    if sorted(got) != list(range(int(cfg["clients"]))) or any(
            n != int(cfg["checked_steps"]) for n in got.values()):
        return False, {"clients_checked": {"value": len(got),
                                           "limit": int(cfg["clients"])}}
    try:
        ref = reference_side(seen, cfg, graph, params0)
    except ValueError as e:
        return False, {"rule_broken": {"value": str(e), "limit": "none"}}
    return compare.verdict(gaps_of(program_side(seen, cfg), ref, params0),
                           limits)


class CompileCount:
    """Programs JAX hands to the compiler (none found in its in-memory
    cache) while counting; a persistent-cache hit counts too."""

    EVENT = "/jax/compilation_cache/compile_requests_use_cache"

    def __init__(self):
        self.n = 0

    def _on(self, name, **_):
        if name == self.EVENT:
            self.n += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_listener(self._on)


def run(cfg: dict, traffic: dict, *, seed: int, seconds: float,
        t_start: float, limits: dict, tracer) -> dict:
    """One run of a training cell: set-up, window, then the check."""
    tr, seen, graph, params0 = build(cfg, seed)
    setup_s = time.perf_counter() - t_start
    work = counts(tr, cfg)

    rounds: list[tuple[float, float]] = []
    tracer.start()
    t0 = time.perf_counter()
    r = 1
    with precision(cfg), CompileCount() as compiles:
        while True:
            left = seconds - (time.perf_counter() - t0)
            if rounds and rounds[-1][1] - rounds[-1][0] > left:
                break
            with tracer.annotate("bench.round"):
                a = time.perf_counter()
                tr.run_round(r, 0.0)
                rounds.append((a, time.perf_counter()))
            r += 1
    device_trace = tracer.stop(rounds)
    memory_peak = tracer.memory_peak()
    print(f"window_compiles {compiles.n}", file=sys.stderr, flush=True)

    del tr
    gc.collect()
    correct, shown = check(seen, cfg, graph, params0, limits)
    return {
        "end_to_end": {"setup_s": setup_s,
                       "round_s": (rounds[-1][1] - t0) / len(rounds)},
        "attempted": len(rounds), "failed": 0,
        "correct": correct, "checks": shown,
        "memory_peak_bytes": memory_peak,
        "layer_ctx": {"rounds": rounds, "work": work,
                      "device": device_trace, "spans": tracer.spans,
                      "round_spans": ROUND_SPANS},
    }
