"""Model families: everything the harness knows about *which model this is*.

A configuration file names its family by `"model_type"`; `harness/spec.py`'s
`family_of(config)` loads `benchmarks/families/<model_type>.py` by file path
(it is never imported as `families.<name>`: one module object a process).
Drivers, readers and tools reach the model through that module alone, so a
later PR brings a new architecture as files: this module, its plain
reference under `benchmarks/reference/`, its configuration, traffic and
limits files and the lines of BENCHMARK.json. `gpt2.py` is one family, not
the pattern to read; what a family module has to offer is this:

Sizes
    dims_of(config) -> dict
        The sizes the rest of the module needs, under the family's own keys,
        all hashable (the reference's programs are cached by them).
        `dims["V"]` is the vocabulary HELD: the load generator draws token
        ids below it (`--vocab`) and the training feed labels below it, so a
        vocabulary cut to a slice is data. A training cell that runs the
        flash kernels also gives `dims["H"]` and `dims["d"]`
        (`layer_metrics/flash_roofline.py` reads head_dim as d // H).

The program's net (through the program's public builders; the benchmark's
seeded weights in the program's layout and the configuration's `param_dtype`,
made on the device in one jitted call whose key is an argument)
    serving_net(config, seed, dims) -> net for `GenerationEngine`
    training_net(config, seed, dims) -> net, initialised, for `fit(feed)`
    give_weights(net, seed, dims, like=None)
        Lay another seed's weights into a net already built (`like`: the
        tree of shapes where the net's own parameters were freed).
        `tools/limits.py` reads a dozen seeds from one compiled step so.

Serving's `correct`
    served_gaps(sample, prompts, seed, dims, lowprec=False) -> [array]
        The plain reference over each sampled request's prompt with its
        served tokens: one float64 array a request, the gap by which each
        served token's reference logit lies below the reference's best.
        `lowprec`: the control's reading at the same positions (the
        reference in the precision below the configuration's). The family
        makes the reference's weights from `seed` itself and decides how
        they are staged: all at once, or a layer at a time where the
        float32 copy exceeds the chip.

Training's `correct` (every tree of readings is {leaf name: scalar or [L]};
both sides give the same names, `harness/compare.py` knows none)
    first_moment_tree(opt_state, params) -> tree like params
    program_sq_norms(tree, dims) -> {name: squared norm}       (jittable)
    program_projections(tree, dims, key) -> {"proj." + name: <x, r>}
    seeded_program_tree(key, dims, like) -> the seeded weights as `like`
    reference_readings(seed, dims, hp, batches, proj_key, lowprec=False,
                       rows=None) -> {"losses", "grad_sq", "grad_proj",
                                      "proj_sq", "change_sq"}
        The plain reference's optimizer steps over `batches` from the same
        seeded weights. "proj_sq" holds the squared gradient norms under
        the projections' names (a leaf projected whole and compared in
        parts is folded here). `lowprec` is the control, `rows` the
        half-batch fault.

Counts (what the algorithm needs, from shapes; they feed `train_mfu`,
`serve_mfu` and `decode_step_roofline`, so a count too high reads over 100 %)
    train_flops_per_token(dims, seq_len)    forward + backward, causal
    prefill_flops(dims, prompt_len)         a whole prompt, head on one row
    decode_flops(dims, context)             one token against `context` keys
    decode_step_min_bytes(dims, live_tokens)  weights once + live cache rows
    kv_bytes_per_token(dims)                cache bytes one token holds
    count_params(dims)                      parameters as held

`harness/weights.py` holds what any family's seeded weights share
(`seed_key`, `fit_program_tree`, `first_moment_tree`, `param_shapes`);
`harness/flops.py` what is about kernels and not about a model.
"""
