"""The plain reference: the pool's write semantics in straightforward
Python, importing nothing of plenum_tpu. Signatures go through OpenSSL
(`cryptography`), hashes through hashlib."""
