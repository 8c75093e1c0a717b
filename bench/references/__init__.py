"""Plain references: one module per architecture family, named by the
``reference`` key of a configuration file, and ``extent``, the approximate
write that every served cache goes through.

A family's module holds everything the harness knows of that architecture,
so that a configuration of a new family is added as files alone:

* ``init_std(path, shape, cfg) -> float``: the deviation of each weight,
  by its name in the program's parameter tree (``harness.weights``); it
  raises ``KeyError`` for a name it has no rule for.
* ``param_count(cfg)``, ``prefill_flops(cfg, prompt_len)``,
  ``decode_flops(cfg, ctx)``, ``decode_kv_bytes(cfg, ctx)`` and
  ``decode_weight_bytes(cfg, batch)``: the work the algorithm needs
  (``harness.counts``); ``cache_bytes(cfg, capacity, max_seq)``, the
  bytes of a pool of ``capacity`` slots, which ``tests/test_sizing.py``
  holds the program's cache to.
* ``served_logits(params, cfg, tokens, prompt_len, stored, precision)``:
  one served request replayed over ``stored``, the program's cache tree
  with the slot axis taken out (every leaf ``(layer stack, position,
  ...)``); returns ``logits`` and ``own``, the reference's values in the
  same tree (``harness.check``).
* ``PLANES``: the priority code of each bit plane of a cache leaf, by the
  leaf's name (the last key of its path); ``LOSS_LEAF``, the leaf whose
  mantissa losses are compared, and ``LOSS_PLANES``, its planes read.
"""
