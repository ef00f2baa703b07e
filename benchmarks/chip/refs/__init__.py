"""Plain references, one module per kind of model, named by a configuration
file's ``reference``.

Each module is the one place that says what its configuration's published
sizes mean, and has:

* ``Sizes.from_config(config)``: the sizes, read from the file's keys;
* ``layout(sizes)``: each parameter leaf's name and shape, as the program
  holds them;
* ``program_fields(sizes)``: the program's ``ModelConfig`` fields that the
  sizes set, by name; a nested dict sets fields of a nested config (``moe``,
  ``mla``), so the module imports nothing of the program;
* ``train_flops_per_token(sizes, seq)``: model FLOPs of one trained token;
* ``attention_dims(sizes)``: ``(n_heads, n_kv_heads, d_qk, d_v)`` of a
  layer's attention call, which ``flops.flash_fwd`` counts;
* ``Reference(sizes, job, precision, devices, mode)``, whose ``run(seed,
  batches)`` trains from the seed's weights and returns ``Readings``.
"""
