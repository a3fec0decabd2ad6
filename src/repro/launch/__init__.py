from .platform import device_fetch, reset_sync_count, sync_count
