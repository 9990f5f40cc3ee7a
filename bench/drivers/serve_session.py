"""Closed-loop turns on a resident, prefilled cache, through the
program's serving engine.

Set-up prefills ``batch`` sessions of ``prompt_len`` ids drawn from the
seed into one cache of ``max_seq`` positions, ``prefill_rows`` rows at a
time (``ServeEngine.prefill`` into a chunk of the cache's rows), and
deploys the greedy sampler; the prefill is outside the window. In the
window each turn feeds one id per row, drawn from the seed, at position
``prompt_len`` and decodes ``new_tokens`` steps greedily
(``ServeEngine.decode`` from the kept cache and position), each token
fetched to the host as it arrives; the next turn starts again at
``prompt_len`` on the same cache, so the prefix is reused and never
recomputed. Traffic keys besides those: ``check_rows``, how many
finished (turn, row) sequences the reference repeats; ``embed_scale``,
the factor on the input embedding's spread; ``trace``: ``start_s`` and
``seconds``.

The configuration file holds the model as it is run at its top level
(the catalog's keys) and the published config under ``published``; set-
up refuses a preset that differs from either, or a parent program that
lacks the preset, before anything is built.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List

import jax
import numpy as np

from bench import harness, mla_moe_weights, weights
from bench.reference import mla_moe_lm

# configuration key -> (ModelConfig field, how the key's value reads there)
PROGRAM_FIELDS = {
    "num_hidden_layers": ("num_layers", None),
    "hidden_size": ("d_model", None),
    "num_attention_heads": ("num_heads", None),
    "num_key_value_heads": ("num_kv_heads", None),
    "q_lora_rank": ("q_lora_rank", lambda v: v or 0),
    "kv_lora_rank": ("kv_lora_rank", None),
    "qk_nope_head_dim": ("qk_nope_head_dim", None),
    "qk_rope_head_dim": ("qk_rope_head_dim", None),
    "v_head_dim": ("v_head_dim", None),
    "intermediate_size": ("d_ff", None),
    "first_k_dense_replace": ("first_k_dense", None),
    "n_routed_experts": ("num_experts", None),
    "num_experts_per_tok": ("experts_per_token", None),
    "moe_intermediate_size": ("moe_d_ff", None),
    "n_shared_experts": ("n_shared_experts", None),
    "scoring_func": ("router_scoring", None),
    "routed_scaling_factor": ("routed_scaling_factor", None),
    "vocab_size": ("vocab_size", None),
    "rms_norm_eps": ("norm_eps", None),
    "rope_theta": ("rope_theta", None),
    "tie_word_embeddings": ("tie_embeddings", None),
}
# keys the program has no field for, held to the one value it computes
FIXED = {"model_type": "deepseek_v3", "hidden_act": "silu",
         "attention_bias": False, "topk_method": "noaux_tc",
         "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
         "moe_layer_freq": 1, "num_nextn_predict_layers": 0,
         "rope_scaling": None}


def program_model(cfg: Dict):
    """The program's preset at the configuration's run sizes, checked key
    by key against them and against the published config."""
    from repro.configs import get_config
    try:
        mc = get_config(cfg["preset"])
    except KeyError as e:
        raise harness.BenchError(f"the program has no preset "
                                 f"{cfg['preset']!r}: {e}") from None
    pub = cfg["published"]
    cut = {k: cfg[k] for k in cfg["reduced"]}
    wrong = {k: (cfg.get(k), v) for k, v in pub.items()
             if k not in cut and cfg.get(k) != v}
    if wrong:
        raise harness.BenchError(f"run keys differ from the published config "
                                 f"without a cut (run, published): {wrong}")
    mc = dataclasses.replace(mc, **{PROGRAM_FIELDS[k][0]: v
                                    for k, v in cut.items()})
    for k, (field, read) in PROGRAM_FIELDS.items():
        want = (read or (lambda v: v))(cfg[k])
        if getattr(mc, field) != want:
            wrong[k] = (getattr(mc, field), want)
    for k, v in FIXED.items():
        if pub.get(k) != v:
            wrong[k] = (v, pub.get(k))
    if wrong:
        raise harness.BenchError(f"preset {cfg['preset']} differs from the "
                                 f"configuration (program, config): {wrong}")
    if mc.param_dtype != cfg["dtypes"]["params"] or \
            mc.dtype != cfg["dtypes"]["compute"]:
        raise harness.BenchError(f"preset dtypes {mc.param_dtype}/{mc.dtype} "
                                 f"are not the configuration's {cfg['dtypes']}")
    return mc


def sizes(cfg: Dict) -> Dict:
    """The run's sizes, as the weights and the reference read them."""
    return {
        "layers": cfg["num_hidden_layers"],
        "dense_layers": cfg["first_k_dense_replace"],
        "d_model": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
        "q_lora_rank": cfg["q_lora_rank"] or 0,
        "kv_lora_rank": cfg["kv_lora_rank"],
        "qk_nope_head_dim": cfg["qk_nope_head_dim"],
        "qk_rope_head_dim": cfg["qk_rope_head_dim"],
        "v_head_dim": cfg["v_head_dim"], "d_ff": cfg["intermediate_size"],
        "experts": cfg["n_routed_experts"],
        "experts_per_token": cfg["num_experts_per_tok"],
        "moe_d_ff": cfg["moe_intermediate_size"],
        "shared_experts": cfg["n_shared_experts"],
        "vocab": cfg["vocab_size"], "eps": cfg["rms_norm_eps"],
        "rope_theta": float(cfg["rope_theta"]),
        "routed_scaling_factor": cfg["routed_scaling_factor"],
    }


class StopWindow(Exception):
    pass


class Driver:
    def __init__(self, cell: harness.Cell, seed: int, clog) -> None:
        self.cell, self.seed, self.clog = cell, seed, clog
        tr = cell.traffic
        self.B, self.P, self.N = tr["batch"], tr["prompt_len"], tr["new_tokens"]
        self.embed_scale = float(tr.get("embed_scale", 1.0))
        self.m = sizes(cell.config)
        self.rng = np.random.default_rng(weights.seed_words(seed, 4))
        self.turns: List[Dict] = []
        self.deploys: List[Dict] = []
        self.expert_loads: List[np.ndarray] = []
        self.attempted = self.failed = 0
        self.trace = self.trace_span = None
        self.t0 = self.t1 = 0.0

    def _params(self):
        return mla_moe_weights.make_params(
            self.seed, self.m, self.embed_scale,
            self.cell.config["dtypes"]["params"])

    def _ids(self, *shape) -> np.ndarray:
        return self.rng.integers(0, self.m["vocab"], shape, dtype=np.int32)

    def setup(self) -> None:
        from repro.launch import serve
        cfg, tr = self.cell.config, self.cell.traffic
        mc = program_model(cfg)
        run = serve.build_run(cfg["preset"], batch=self.B,
                              max_seq=tr["max_seq"])
        engine, prog_params = serve.build_server(
            dataclasses.replace(run, model=mc))
        like = jax.tree.map(lambda a: (a.shape, a.dtype), prog_params)
        del prog_params
        self.engine = engine
        self.params = self._params()
        if jax.tree.map(lambda a: (a.shape, a.dtype), self.params) != like:
            raise harness.BenchError("the program's parameter layout is not "
                                     "the checkpoint layout "
                                     "mla_moe_weights.shapes gives")
        self.greedy = engine.deploy_sampler(serve.GREEDY_SAMPLER)
        self.prefix = self._ids(self.B, self.P)
        cache = engine.model.init_cache(self.B, tr["max_seq"], engine.ctx)
        rows = tr["prefill_rows"]
        with harness.span("prefill"):
            for r0 in range(0, self.B, rows):
                _, cache, _ = engine.prefill(
                    self.params, jax.numpy.asarray(self.prefix[r0:r0 + rows]),
                    cache=cache, row=r0)
        with harness.span("warm_up"):     # one whole turn, counter on
            engine.expert_load.on = True
            toks, cache, _, _ = engine.decode(
                self.params, jax.numpy.zeros((self.B,), np.int32), cache,
                self.P, self.N)
            np.asarray(toks)
            engine.expert_load.on = False
            engine.expert_load.drain()
        self.cache = cache

    def window(self, seconds: float, tracer: harness.Tracer) -> None:
        engine, tc = self.engine, self.cell.traffic["trace"]
        state = {"stop_after": None}
        self.t0 = t_start = time.perf_counter()
        deadline = t_start + seconds

        def stop_trace() -> None:
            tracer.stop()
            engine.expert_load.on = False

        def on_token(i: int, tok) -> None:
            with harness.span("token_fetch"):
                np.asarray(tok)
            now = time.perf_counter()
            turn["arrivals"].append(now)
            if state["stop_after"] is not None and now >= state["stop_after"]:
                stop_trace()
            if tracer.on and tracer.started is None \
                    and now - t_start >= tc["start_s"]:
                tracer.start()
                engine.expert_load.on = True
                state["stop_after"] = now + tc["seconds"]
            if now >= deadline:
                raise StopWindow

        while time.perf_counter() < deadline:
            turn = {"first": self._ids(self.B), "arrivals": [],
                    "tokens": None, "md5s": None}
            try:
                with harness.span("turn"):
                    toks, self.cache, _, info = engine.decode(
                        self.params, jax.numpy.asarray(turn["first"]),
                        self.cache, self.P, self.N, on_token=on_token)
                    turn["tokens"] = np.asarray(toks)
                turn["md5s"] = info["sampler_md5s"]
                self.turns.append(turn)
            except StopWindow:
                self.turns.append(turn)
                break
        self.t1 = max(t["arrivals"][-1] for t in self.turns if t["arrivals"])
        stop_trace()
        self.expert_loads = engine.expert_load.drain()

    # -- results ------------------------------------------------------------
    def end_to_end(self) -> Dict[str, float]:
        fetched = sum(len(t["arrivals"]) for t in self.turns)
        return {"decode_tokens_per_s": self.B * fetched / (self.t1 - self.t0)}

    def traced_positions(self) -> List[int]:
        """The position each traced decode step wrote and attended up to."""
        if self.trace_span is None:
            return []
        lo, hi = self.trace_span
        return [self.P + i for t in self.turns
                for i, a in enumerate(t["arrivals"]) if lo <= a <= hi]

    def release(self) -> None:
        self.engine = self.params = self.cache = None

    def _audit(self) -> List[tuple]:
        """Every finished turn's tokens and sampler versions; returns the
        (turn, row) pairs, all greedy."""
        V, rows = self.m["vocab"], []
        for ti, t in enumerate(self.turns):
            self.attempted += self.B
            if t["tokens"] is None:
                continue
            toks = t["tokens"]
            ok = all(x == self.greedy.md5 for x in t["md5s"]) \
                and toks.shape == (self.B, self.N) \
                and int(toks.min()) >= 0 and int(toks.max()) < V
            if not ok:
                self.failed += self.B
                continue
            rows += [(ti, r) for r in range(self.B)]
        return rows

    def sample(self) -> List[tuple]:
        """Finished (turn, row) pairs drawn from the seed."""
        rows = self._audit()
        n = min(self.cell.traffic["check_rows"], len(rows))
        rng = np.random.default_rng(weights.seed_words(self.seed, 3))
        return [rows[j] for j in rng.choice(len(rows), n, replace=False)]

    def gaps(self, picks: List[tuple], mode: str = "f32") -> np.ndarray:
        """How far each served token's logit lies below the f32
        reference's best, over the prefix, the fed id and the served ids
        of each picked sequence (or, with another ``mode``, the tokens
        that mode puts first), all positions of all picks."""
        params = self._params()
        out = []
        for ti, r in picks:
            t = self.turns[ti]
            prompt = np.concatenate([self.prefix[r], t["first"][r:r + 1]])
            out.append(mla_moe_lm.served_gaps(params, prompt, t["tokens"][r],
                                              self.m, mode))
        return np.concatenate(out)

    def widest_gap(self, picks: List[tuple], mode: str = "f32") -> float:
        return float(self.gaps(picks, mode).max())

    def check(self) -> Dict[str, tuple]:
        """The widest gap and the mean gap over every checked position,
        each compared where the limits name it: a routing decision that
        bf16 rounding flips moves a few positions far (the widest gap of
        the program and of the fp8 control lie close), while a lower
        precision moves most positions (the means lie far apart)."""
        lim = self.cell.limits
        picks = self.sample()
        g = self.gaps(picks) if picks else np.array([np.inf])
        return {"logit_gap": (float(g.max()), lim.get("logit_gap")),
                "logit_gap_mean": (float(g.mean()), lim.get("logit_gap_mean"))}
