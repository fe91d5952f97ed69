"""The plain Granite-4.0-H reference against transformers' own model.

``reference/granite_hybrid.py`` is built from the published equations; here
it meets ``GraniteMoeHybridForCausalLM`` (torch, CPU, float32, the eager
attention and the naive SSD path) on a tiny configuration of the same
form, with the same weights, at every position of one sequence.
"""

import json
from pathlib import Path

import jax
import numpy as np
import pytest

from chipbench.drivers import serve_hybrid as SH
from chipbench.reference import granite_hybrid as ref

torch = pytest.importorskip("torch")
hf = pytest.importorskip(
    "transformers.models.granitemoehybrid.modeling_granitemoehybrid")

DATA = Path(__file__).resolve().parent / "data"


def tiny_conf(groups: int):
    c = json.loads((DATA / "configs" / "tiny-granite.json").read_text())
    c["mamba_n_groups"] = groups
    return c


def hf_model(c, w):
    from transformers import GraniteMoeHybridConfig

    hc = GraniteMoeHybridConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        intermediate_size=c["intermediate_size"],
        shared_intermediate_size=c["shared_intermediate_size"],
        num_hidden_layers=c["num_hidden_layers"],
        num_attention_heads=c["num_attention_heads"],
        num_key_value_heads=c["num_key_value_heads"],
        rms_norm_eps=c["rms_norm_eps"], tie_word_embeddings=True,
        embedding_multiplier=c["embedding_multiplier"],
        logits_scaling=c["logits_scaling"],
        residual_multiplier=c["residual_multiplier"],
        attention_multiplier=c["attention_multiplier"],
        num_local_experts=0, num_experts_per_tok=0,
        position_embedding_type="nope", layer_types=c["layer_types"],
        mamba_n_heads=c["mamba_n_heads"], mamba_n_groups=c["mamba_n_groups"],
        mamba_d_state=c["mamba_d_state"], mamba_d_head=c["mamba_d_head"],
        mamba_d_conv=c["mamba_d_conv"], mamba_expand=c["mamba_expand"],
        mamba_chunk_size=c["mamba_chunk_size"], mamba_conv_bias=True,
        mamba_proj_bias=False, attn_implementation="eager")
    m = hf.GraniteMoeHybridForCausalLM(hc).float().eval()
    d = ref.Dims.of(c)
    t = lambda a: torch.tensor(np.asarray(a, np.float32))
    sd = {"model.embed_tokens.weight": t(w["embed"]),
          "model.norm.weight": t(w["final_norm"]),
          "lm_head.weight": t(w["embed"])}
    hq, hkv, hd, di = d.hq, d.hkv, d.hd, d.di
    for i in range(len(d.layer_types)):
        kind, per, j = d.where(i)
        lw = jax.tree.map(lambda a: np.asarray(a[per, j], np.float32),
                          w["layers"][kind])
        p = f"model.layers.{i}."
        sd[p + "input_layernorm.weight"] = t(lw["ln1"])
        sd[p + "post_attention_layernorm.weight"] = t(lw["ln2"])
        sd[p + "shared_mlp.input_linear.weight"] = t(lw["mlp"]["w_in"].T)
        sd[p + "shared_mlp.output_linear.weight"] = t(lw["mlp"]["w_out"].T)
        if kind == "mamba":
            mw = lw["mamba"]
            q = p + "mamba."
            sd[q + "in_proj.weight"] = t(np.concatenate(
                [mw["w_xz"][:, di:], mw["w_xz"][:, :di], mw["w_bcdt"]], 1).T)
            sd[q + "conv1d.weight"] = t(mw["conv_w"].T[:, None, :])
            sd[q + "conv1d.bias"] = t(mw["conv_b"])
            sd[q + "dt_bias"] = t(mw["dt_bias"])
            sd[q + "A_log"] = t(mw["a_log"])
            sd[q + "D"] = t(mw["skip_d"])
            sd[q + "norm.weight"] = t(mw["norm"])
            sd[q + "out_proj.weight"] = t(mw["w_out"].T)
        else:
            aw = lw["attn"]
            q = p + "self_attn."
            sd[q + "q_proj.weight"] = t(aw["wqkv"][:, :hq * hd].T)
            sd[q + "k_proj.weight"] = t(aw["wqkv"][:, hq * hd:(hq + hkv) * hd].T)
            sd[q + "v_proj.weight"] = t(aw["wqkv"][:, (hq + hkv) * hd:].T)
            sd[q + "o_proj.weight"] = t(aw["wo"].T)
    missing, unexpected = m.load_state_dict(sd, strict=False)
    assert not unexpected and set(missing) <= {"lm_head.weight"}, missing
    return m


@pytest.mark.parametrize("groups", [1, 2])
def test_reference_matches_transformers(groups):
    """Every position of a 45-token sequence (not a multiple of the
    chunk, so transformers' SSD pads): float32 against float32, the two
    differing only in the order of the recurrence's sums (chunked there,
    sequential here), so 1e-4 of the logits' scale."""
    c = tiny_conf(groups)
    d = ref.Dims.of(c)
    w = SH.init_weights(2**31 + 5, d, "float32")
    toks = np.random.default_rng(3).integers(0, d.vocab, 45).astype(np.int32)
    ours = ref.logits_at(w, d, toks, list(range(len(toks))))
    with torch.no_grad():
        theirs = hf_model(c, w)(torch.tensor(toks[None].astype(np.int64))
                                ).logits[0].numpy()
    scale = np.abs(theirs).max()
    np.testing.assert_allclose(ours, theirs, atol=1e-4 * scale, rtol=0)


@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_program_serving_matches_the_reference(backend):
    """The program's prefill and then its token-by-token decode through
    ``Scheduler`` (two slots, E4M3 KV on the attention layer, fp32 Mamba
    state), every logits row it samples from against the reference's at
    that position.  The program runs bf16 operands and activations and E4M3
    keys and values over bf16 weights, the reference float32 over the same
    weight values: 10% of each row's norm, where the program reads 2-5% at
    this size and the reference at E4M3 operands (the control) reads more
    on every row."""
    from repro.core import engine
    from repro.serving import scheduler as S

    c = json.loads((DATA / "configs" / "tiny-granite.json").read_text())
    d = ref.Dims.of(c)
    cfg = SH.program_config(c)
    w = SH.init_weights(2**31 + 7, d, cfg.param_dtype)
    rng = np.random.default_rng(5)
    reqs = [S.Request(rid=i, arrival=0.0,
                      prompt=rng.integers(0, d.vocab, n).astype(np.int32),
                      max_new_tokens=m)
            for i, (n, m) in enumerate([(21, 9), (33, 5)])]
    rows = {r.rid: [] for r in reqs}
    errs, cerrs = [], []
    with engine.use_backend(backend):
        sched = S.Scheduler(w, cfg, S.SchedulerConfig(
            n_slots=2, max_len=64, storage_dtype="float8_e4m3fn"))
        real = sched._sample

        def keep(logits):
            for i, s in enumerate(sched.slots):
                if s is not None:
                    rows[s.rid].append(np.array(logits[i], np.float32))
            real(logits)

        sched._sample = keep
        sched.submit(reqs)
        res = {r.rid: r for r in sched.run()}
    for r in reqs:
        toks = res[r.rid].tokens
        seq = np.concatenate([r.prompt, toks]).astype(np.int32)
        P = len(r.prompt)
        # decode step k reads tokens[: k + 1] and gives the logits at P + k
        at = list(range(P, P + len(rows[r.rid])))
        want = ref.logits_at(w, d, seq, at)
        ctrl = ref.logits_at(w, d, seq, at, fp8=True)
        for got, exp, c8 in zip(rows[r.rid], want, ctrl):
            errs.append(np.linalg.norm(got - exp) / np.linalg.norm(exp))
            cerrs.append(np.linalg.norm(c8 - exp) / np.linalg.norm(exp))
    assert max(errs) < 0.1 < min(cerrs), (max(errs), min(cerrs))
