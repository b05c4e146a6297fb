"""One module a decoder family: its spec function (a ``config.json`` ->
:class:`~tensorflowonspark_tpu.models.transformer.DecoderSpec`), registered
with ``get_model`` by
:func:`~tensorflowonspark_tpu.models.transformer.register_decoder`.  A new
family is a file here and an import below."""

from tensorflowonspark_tpu.models.families import (  # noqa: F401
    deepseek_v2, keye_vl2, lfm2_moe, mellum2, nemotron_h, olmo_hybrid)
