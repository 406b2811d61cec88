"""Pass registry population: importing this package registers every
built-in pass.  To add a pass: new module here, subclass
:class:`~deepspeed_tpu.analysis.core.LintPass`, decorate with
``@register``, import it below, seed a bad/good fixture twin under
``tests/unit/analysis/fixtures/`` (README "how to add a pass")."""

from deepspeed_tpu.analysis.passes import (  # noqa: F401
    donation, host_sync, metric_names, pallas_dma, pallas_tile, recompile,
    sharding_contract, slo_rules, typed_errors, vmem_budget)
