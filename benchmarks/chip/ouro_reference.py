"""The plain reference of ``Ouro-2.6B`` (ByteDance, ``model_type`` ``ouro``,
family "Ouro 1.4B/2.6B LoopLM"), with its seeded weights and its checks.

``logits`` is the forward pass as the published ``config.json`` and the
family's description give it, in straightforward ``jax.numpy`` and
float32 (callers set ``jax.default_matmul_precision("highest")``), with no
kernel, cache or batching, and shares nothing with ``horovod_tpu/models``
but the names of the parameter tree it is handed.  ``N`` is an RMSNorm
with a learned scale, eps ``rms_norm_eps``:

    x = E[token]
    pass u = 1 .. total_ut_steps, layer i = 1 .. num_hidden_layers, the
    SAME weights of layer i in every pass:
        x = x + N_i^a2( Attn_i,u( N_i^a1(x) ) )
        x = x + N_i^m2( W_down,i( silu(W_gate,i n) * W_up,i n ) ),
            n = N_i^m1(x)
    and at the end of every pass x = N_final(x)
    logits = W_head x                       (after the last pass; untied)

``Attn_i,u(h)``: ``q, k, v = W_q h, W_k h, W_v h`` (``num_attention_heads``
over as many key-value heads of ``head_dim``, no bias), rotary positions
on all of a head's channels at base ``rope_theta``, pairs ``(j, j +
head_dim / 2)``, ``softmax(q k^T / sqrt(head_dim) + causal) v``, then
``W_o``.  Pass ``u``'s keys and values are its own: nothing is carried
from one pass to the next but ``x``, so the plain forward needs no cache
to say so (the program's per-pass cache is what the comparison holds it
to).  The sandwich norms, the final norm at the end of every pass and
the separate cache of every pass are the family's (the Ouro paper and
its modeling code), not the row's keys; the early-exit gate decides
nothing at ``early_exit_threshold`` 1 and is left out.

``weights`` makes the tree the replica is handed: bfloat16, a normal law
of variance one over the fan-in for every linear map and the embedding,
every norm's scale uniform on 0.5 to 1.5 (a normed vector of unit
variance is its own RMSNorm, so scales of ones would let a program that
leaves a norm out pass).

``served_gap`` is the comparison every run of the cell makes: the 7B
cell's method (``reference.lm_served_gap``: for every served token the
gap by which its logit lies below the reference's best at its
position), the reference a layer at a time (one jitted function is
handed a layer's bfloat16 weights and casts them to float32 itself, so
that one layer is on the device in float32 at once); and the MiMo cell's
replay (``mimo_v2_reference.py``), each (pass, layer) apart: greedy
streams of random weights settle on repeating a token, and then the
logits hardly see what a pass's attention read.  Its control is the
reference with both operands of every linear map rounded to 8 bits
(``reference.int8``).  ``check`` is ``--check reference``.
"""
from __future__ import annotations

import math

import reference
from mimo_v2_reference import rotary, sample_positions
from solar_open2_reference import linear

TOLERANCE = 0.025


def attention(layer, x, cfg: dict, operands=None):
    """``Attn(h)`` of one layer and pass, ``x`` [B, T, d] its normalised
    input -> [B, T, d]."""
    import jax
    import jax.numpy as jnp
    attn = layer["attn"]
    q, k, v = (linear("btd,dhk->bthk", x, -1, attn[name]["kernel"], 0,
                      operands) for name in ("wq", "wk", "wv"))
    theta = cfg["rope_theta"]
    q, k = reference.rotary(q, theta), reference.rotary(k, theta)
    t = x.shape[1]
    scores = jnp.einsum("bqhk,bshk->bhqs", q, k) / math.sqrt(cfg["head_dim"])
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    mixed = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(scores, -1), v)
    return linear("bthk,hkd->btd", mixed, (-2, -1), attn["wo"]["kernel"],
                  (0, 1), operands)


def block(layer, x, cfg: dict, operands=None):
    """One layer, both sub-layers, each between its two norms."""
    import jax
    eps = cfg["rms_norm_eps"]

    def norm(name, y):
        return reference.rms_norm(y, layer[name]["scale"], eps)

    x = x + norm("mixer_post_norm", attention(
        layer, norm("mixer_norm", x), cfg, operands))
    n = norm("mlp_norm", x)
    mlp = layer["mlp"]
    hidden = jax.nn.silu(linear("btd,df->btf", n, -1, mlp["gate"]["kernel"],
                                0, operands)) \
        * linear("btd,df->btf", n, -1, mlp["up"]["kernel"], 0, operands)
    return x + norm("mlp_post_norm", linear(
        "btf,fd->btd", hidden, -1, mlp["down"]["kernel"], 0, operands))


def final_norm(params, x, cfg: dict):
    return reference.rms_norm(x, params["final_norm"]["scale"],
                              cfg["rms_norm_eps"])


def head(params, x, operands=None):
    return linear("btd,dv->btv", x, -1, params["lm_head"]["kernel"], 0,
                  operands)


def logits(params, tokens, cfg: dict, operands=None):
    """tokens [B, T] -> logits [B, T, vocab], float32."""
    x = params["embed"]["embedding"][tokens]
    for _ in range(cfg["total_ut_steps"]):
        for i in range(cfg["num_hidden_layers"]):
            x = block(params[f"layer_{i}"], x, cfg, operands)
        x = final_norm(params, x, cfg)
    return head(params, x, operands)


# ---------------------------------------------------------------- the weights
def weights(run):
    """The configuration's weights from the seed, made on the device a
    layer at a time (one compiled program, each layer's key); the tree
    has the names the program's hybrid decoder gives its parameters and
    nothing else of the program."""
    import jax
    import jax.numpy as jnp

    cfg = run.config
    dtype = run.resolve(cfg["model"]["args"]["param_dtype"][1:])
    d, ff, vocab = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["vocab_size"]
    heads, kv, width = cfg["num_attention_heads"], \
        cfg["num_key_value_heads"], cfg["head_dim"]
    normal = lambda fan_in, *shape: ("normal", shape, fan_in)   # noqa: E731
    scale = ("norm", (d,), 0)
    layer = {"mixer_norm": {"scale": scale},
             "mixer_post_norm": {"scale": scale},
             "mlp_norm": {"scale": scale},
             "mlp_post_norm": {"scale": scale},
             "attn": {"wq": {"kernel": normal(d, d, heads, width)},
                      "wk": {"kernel": normal(d, d, kv, width)},
                      "wv": {"kernel": normal(d, d, kv, width)},
                      "wo": {"kernel": normal(heads * width, heads, width,
                                              d)}},
             "mlp": {"gate": {"kernel": normal(d, d, ff)},
                     "up": {"kernel": normal(d, d, ff)},
                     "down": {"kernel": normal(ff, ff, d)}}}
    outer = {"embed": {"embedding": normal(d, vocab, d)},
             "final_norm": {"scale": scale},
             "lm_head": {"kernel": normal(d, d, vocab)}}

    def draw(key, law, shape, fan_in):
        if law == "normal":
            return (fan_in ** -0.5 * jax.random.normal(
                key, shape, jnp.float32)).astype(dtype)
        return jax.random.uniform(key, shape, jnp.float32, 0.5,
                                  1.5).astype(dtype)

    def maker(tree):
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            tree, is_leaf=lambda x: isinstance(x, tuple))
        return jax.jit(lambda key: jax.tree_util.tree_unflatten(treedef, [
            draw(jax.random.fold_in(key, at), *spec)
            for at, (_, spec) in enumerate(flat)]))

    key = jax.random.key(run.seed)
    params = maker(outer)(jax.random.fold_in(key, 0))
    a_layer = maker(layer)
    for i in range(cfg["num_hidden_layers"]):
        params[f"layer_{i}"] = a_layer(jax.random.fold_in(key, 1 + i))
    return params


# ------------------------------------------------- what every run compares
def softmax_at(q, k, v, at, cfg: dict):
    """One (pass, layer)'s attention as the equations have it, over what
    the program fed it: queries ``q`` [S, H, D] at the positions ``at``
    [S], every position's keys and values ``k``, ``v`` [T, KV, D] (all
    before positions), each query over the keys at or before it ->
    float32 [S, H, D]."""
    import jax
    import jax.numpy as jnp
    width, theta, t = cfg["head_dim"], cfg["rope_theta"], k.shape[0]
    q = rotary(q[None], at, width, theta)[0]
    k = rotary(k[None], jnp.arange(t), width, theta)[0]
    scores = jnp.einsum("shd,thd->sht", q, k) / math.sqrt(cfg["head_dim"])
    scores = jnp.where(jnp.arange(t) <= at[:, None, None], scores, -jnp.inf)
    return jnp.einsum("sht,thd->shd", jax.nn.softmax(scores, -1), v)


FED = ("q", "k", "v", "out")
KEYS = ("k", "v")                          # kept at every position
SAMPLED = ("q", "out")                     # kept at the sampled positions


def replay(cfg: dict):
    """The program, replayed on one stream it served: a function of
    ``(params, tokens [1, T], first, end)`` that prefills the prompt
    ``tokens[0, :first]`` as the replica does (a batch of one, padded to
    a bucket, here the widest, the true length passed) and feeds
    ``tokens[0, first:end - 1]`` to the family's decode step, one token
    at a time in a cache of one slot, every pass through its own leaves.
    It returns the program's ``logits`` [T, vocab] (position t predicts
    token t + 1; rows outside ``first - 1 .. end - 2`` are zeros); the
    keys and values every (pass, layer)'s softmax was fed, ``k`` and
    ``v`` [passes x layers, T, KV, D] (before positions, pass-major);
    and at the ``at`` [samples] positions of ``sample_positions`` the
    queries it was fed and what came out, ``q`` and ``out`` [passes x
    layers, samples, H, D].  The model is built from the configuration's
    file as ``run.py`` builds it."""
    import jax
    import jax.numpy as jnp
    import run as harness

    config = harness.resolve(cfg["model"]["config"])(**{
        **harness.build_args(cfg), "decode": True,
        "max_seq_len": cfg["serve"]["max_seq"]})
    family = config.family
    model = family.build(config)
    bucket = max(cfg["serve"]["warmup_buckets"])     # one shape for all
    samples = cfg["served_check"]["attend_samples"]
    layers = cfg["num_hidden_layers"]

    def fed_to(sown, name):                # [passes x layers, T', ...]
        # The passes the program ran, whatever the file says: a program
        # a pass short is then held to its logits.
        fed = [sown["attention"][f"layer_{i}"]["attn"][name]
               for i in range(layers)]
        return jnp.stack([fed[i][u][0] for u in range(len(fed[0]))
                          for i in range(layers)])

    def run(params, tokens, first, end):
        variables = {"params": params}
        positions = tokens.shape[1]
        at, _ = sample_positions(first, end, samples)
        slot_of = jnp.full(positions, samples, jnp.int32) \
            .at[at].set(jnp.arange(samples))     # the last of a repeat
        prompt = jnp.where(jnp.arange(bucket) < first, tokens[:, :bucket], 0)
        sown = {"attention": {}}
        logits_, cache = family.prefill(model, variables, prompt,
                                        lengths=first, sown=sown)
        rows = jnp.zeros((positions, logits_.shape[-1]), jnp.float32) \
            .at[first - 1].set(logits_[0, first - 1].astype(jnp.float32))
        keys = {name: jnp.zeros((fed_to(sown, name).shape[0], positions,
                                 *fed_to(sown, name).shape[2:]),
                                fed_to(sown, name).dtype)
                .at[:, :bucket].set(fed_to(sown, name)) for name in KEYS}
        some = {name: jnp.zeros((fed_to(sown, name).shape[0], samples + 1,
                                 *fed_to(sown, name).shape[2:]),
                                fed_to(sown, name).dtype)
                .at[:, slot_of[:bucket]].set(fed_to(sown, name))
                for name in SAMPLED}

        def step(pos, carry):
            cache, rows, keys, some = carry
            sown = {"attention": {}}
            logits_, cache = family.decode_step(
                model, variables, cache,
                jax.lax.dynamic_slice_in_dim(tokens, pos, 1, axis=1),
                sown=sown)
            return (cache,
                    rows.at[pos].set(logits_[0, 0].astype(jnp.float32)),
                    {name: keys[name].at[:, pos].set(fed_to(sown, name)[:, 0])
                     for name in KEYS},
                    {name: some[name].at[:, slot_of[pos]].set(
                        fed_to(sown, name)[:, 0]) for name in SAMPLED})

        _, rows, keys, some = jax.lax.fori_loop(first, end - 1, step,
                                                (cache, rows, keys, some))
        return {"logits": rows, **keys,
                **{name: value[:, :samples] for name, value in some.items()}}

    jitted = jax.jit(run)

    def replayed(params, tokens, first, end):
        if first > bucket:
            raise ValueError(f"a prompt of {first} tokens in a bucket of "
                             f"{bucket}")
        return jitted(params, tokens, first, end)

    return replayed


def served_gap(cfg: dict, control: bool = False):
    """The comparison of one finished request with the reference, as a
    function of ``(params, tokens, first, length)`` with the arguments of
    ``reference.lm_served_gap``.  It returns, each a number and, where it
    is a mean over the served tokens, its ``_sum``:

    - ``gap``, ``gap_sum``: the 7B cell's, over every served token: the
      gap by which its logit lies below the reference's best;
    - ``replay_err``: the stream replayed through the program (``replay``,
      its first ``served_check.replay_steps`` decode steps) against the
      reference, the root mean square over the vocabulary of the logits'
      difference over the reference's, the worst position;
    - ``attend_gap``: each (pass, layer)'s attention output at
      ``served_check.attend_samples`` positions of the replay against the
      float32 softmax over what the program fed its own (its queries,
      keys and values, before positions), the norm of the difference over
      the norm, the worst (pass, layer): what holds every pass to a cache
      of its own and every decode step's row to being written, which with
      random weights the logits may hardly see.

    With ``control`` each also as ``control_...``: the tokens, and the
    logits, of the reference computed in int8; what the program fed its
    own is not the control's to round."""
    import jax
    import jax.numpy as jnp

    check = cfg["served_check"]
    samples, steps = check["attend_samples"], check["replay_steps"]
    replayed = replay(cfg)

    def full(tree):
        return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)

    def block_fn(operands):
        def run_block(layer, x):
            with jax.default_matmul_precision("highest"):
                return block(full(layer), x, cfg, operands)
        return jax.jit(run_block)

    def end_fn(operands):
        def run_end(outer, x):      # the last pass's final norm, the head
            with jax.default_matmul_precision("highest"):
                return head(full(outer), final_norm(full(outer), x, cfg),
                            operands)[0]
        return jax.jit(run_end)

    between = jax.jit(lambda outer, x: final_norm(full(outer), x, cfg))
    embed = jax.jit(lambda outer, tokens: outer["embed"]["embedding"][
        tokens].astype(jnp.float32))
    passes = {"": None, **({"control_": reference.int8} if control else {})}
    blocks = {name: block_fn(how) for name, how in passes.items()}
    ends = {name: end_fn(how) for name, how in passes.items()}

    def forward(name, params, tokens):
        outer = {key: params[key]
                 for key in ("embed", "final_norm", "lm_head")}
        x = embed(outer, tokens)
        for u in range(cfg["total_ut_steps"]):
            for i in range(cfg["num_hidden_layers"]):
                x = blocks[name](params[f"layer_{i}"], x)
            if u + 1 < cfg["total_ut_steps"]:
                x = between(outer, x)
        return ends[name](outer, x)

    @jax.jit
    def read(logits_, chosen, live):
        below = jnp.where(live, jnp.max(logits_, -1) - jnp.take_along_axis(
            logits_, chosen[:, None], -1)[:, 0], 0.0)
        return jnp.max(below), jnp.sum(below)

    @jax.jit
    def off(got, want, live):
        """The root mean square over the vocabulary of the difference,
        over that of ``want``, the worst live position."""
        rms = lambda x: jnp.sqrt(jnp.mean(x * x, -1))        # noqa: E731
        return jnp.max(jnp.where(live, rms(got - want) / rms(want), 0.0))

    @jax.jit
    def attend_gap(q, k, v, out, at, counts):
        """One (pass, layer)'s output at the sampled positions against
        the float32 softmax over what it was fed."""
        with jax.default_matmul_precision("highest"):
            want = softmax_at(*(each.astype(jnp.float32)
                                for each in (q, k, v)), at, cfg)
        keep = counts[:, None, None]
        return jnp.linalg.norm(jnp.where(keep, out - want, 0.0)) \
            / jnp.linalg.norm(jnp.where(keep, want, 0.0))

    def gaps(params, tokens, first, length):
        tokens = jnp.asarray(tokens)
        at = jnp.arange(tokens.shape[1])
        live = (at >= first - 1) & (at < length - 1)   # t predicts t + 1
        end = jnp.minimum(length, first + steps + 1)
        replay_live = live & (at < end - 1)
        logits_ = forward("", params, tokens)
        gap, gap_sum = read(logits_, jnp.roll(tokens[0], -1), live)
        program = replayed(params, tokens, first, end)
        sampled, counts = sample_positions(first, end, samples)
        worst = max(float(attend_gap(
            *(program[name][pair] for name in FED), sampled, counts))
            for pair in range(program["q"].shape[0]))
        seen = {"gap": gap, "gap_sum": gap_sum,
                "replay_err": off(program["logits"], logits_, replay_live),
                "attend_gap": worst}
        if control:
            lower = forward("control_", params, tokens)
            seen["control_gap"], seen["control_gap_sum"] = read(
                logits_, jnp.argmax(lower, -1), live)
            seen["control_replay_err"] = off(lower, logits_, replay_live)
            seen["control_attend_gap"] = worst
        return seen

    return gaps


# -------------------------------------------------------- --check reference
def check(run, cfg: dict) -> dict:
    """Prefill one prompt as the executor does (a batch of one, padded to
    its bucket, the true length passed), insert it into a slot of a slot
    cache of 4, then decode through the cache, every pass through its own
    leaves; every row against ``logits``.  Two layers, all the passes."""
    import types

    import jax
    import jax.numpy as jnp

    layers = 2
    positions = 512 if cfg["hidden_size"] > 1024 else 64
    prompt, decoded = positions * 5 // 8 - 3, positions // 4
    slots, slot = 4, 3
    cfg = {**cfg, "num_hidden_layers": layers}
    config = run.model_config(layer_types=("attention",) * layers,
                              decode=True, max_seq_len=positions)
    family = config.family
    model = family.build(config)
    tokens = jax.random.randint(jax.random.key(run.seed), (1, positions),
                                2, cfg["vocab_size"])
    params = weights(types.SimpleNamespace(config=cfg, seed=run.seed,
                                           resolve=run.resolve))
    padded = tokens.at[:, prompt:].set(0)           # the bucket's padding
    logits_, cache1 = jax.jit(lambda p, t: family.prefill(
        model, {"params": p}, t, lengths=prompt))(params, padded)
    rows = [logits_[0, prompt - 1]]
    cache = jax.tree_util.tree_map(
        lambda big, small: big.at[slot].set(small[0]),
        jax.jit(lambda p: family.fresh_cache(model, p, slots))(params),
        cache1)
    decode = jax.jit(lambda p, c, t: family.decode_step(
        model, {"params": p}, c, t))
    for at in range(prompt, prompt + decoded):
        fed = jnp.zeros((slots, 1), jnp.int32).at[slot, 0].set(
            tokens[0, at])
        logits_, cache = decode(params, cache, fed)
        rows.append(logits_[slot, 0])
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: logits(p, tokens[:, :prompt + decoded],
                                        cfg))(
            jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params))
    return {"compared": f"{len(rows)} logit rows (prefill of {prompt} "
                        f"tokens in a bucket of {positions} into slot "
                        f"{slot}, then {decoded} decoded through the "
                        f"cache) x {cfg['vocab_size']}",
            "layers": layers, "passes": cfg["total_ut_steps"],
            "positions": positions, "tolerance": TOLERANCE,
            "error": reference.error(jnp.stack(rows),
                                     want[0, prompt - 1:])}
