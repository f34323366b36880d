"""One module per architecture, named by the configuration file's
``model_type``: ``bench/arch/<model_type>.py``, loaded by path
(``harness.config.architecture``).  Each provides what the yardstick needs
of its architecture:

* ``arch(c) -> dict``: the shapes, from the configuration file's keys;
* ``model_config(c, name)``: the program's ``ModelConfig``;
* ``logits(arch, seed, seqs, rows, *, mode)``: the plain reference's
  next-token logits at ``rows`` of ``seqs``, ``mode="fp32"`` for the check
  that decides ``correct`` and ``mode="fp8"`` for the control;
* ``step_flops(arch, decode_rows, chunks) -> int``: the model FLOPs of one
  tick's query rows, ``decode_rows`` each decode row's attended context
  length and ``chunks`` the ``(start, count)`` of each prefill chunk, as in
  ``harness.tap.TickWork``.

A new architecture is a new module here; no file of the harness changes."""
