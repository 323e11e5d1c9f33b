"""One runner per configuration ``kind``: ``train`` and ``serve``."""
