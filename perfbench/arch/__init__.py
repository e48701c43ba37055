"""One module per published ``model_type``: ``arch/<model_type>.py``.

``harness.load_cell`` finds a configuration's module by the ``model_type``
its file states.  Each module exports:

    model_config(spec)            the program's ``ModelConfig``
    Reference(spec, *, fp8=False) the plain float32 reference:
                                  ``served_logits``, ``grads``, ``train``
    step_flops(spec, r, ft_lens, pf_rows, dec_ctx)
                                  model FLOPs of one unified step
    NORMS                         leaf names drawn as ones

Only these modules read a configuration's shape keys.
"""
