"""Device ms per train step of the flash-attention kernels, by self time,
from the raw trace: the forward ``flash_attention`` and the backward
``flash_attention_dq`` and ``flash_attention_dkv``. Nothing where the
step ran none of them."""
from bench import scopes

KERNELS = ("flash_attention", "flash_attention_dq", "flash_attention_dkv")


def read(drv):
    st = scopes.traced_step(drv, r"train_step")
    if st is None:
        return None
    secs = sum(st.kernel_s(k) for k in KERNELS)
    return st.per_run_ms(secs) if secs > 0 else None
