"""OpenSSL's verdict (through ``cryptography``) on a reply signed with
Ed25519: ``replica_pubs`` gives each replica's public key, 32 raw bytes;
the program signs the SHA-256 digest of the bytes it authenticates
(``sample/authentication/authenticator.py::Ed25519Scheme``), so that
digest is the message."""

import hashlib


class ReplyVerifier:
    def __init__(self, replica_pubs: dict):
        from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PublicKey

        self._keys = {
            rid: Ed25519PublicKey.from_public_bytes(bytes(pub))
            for rid, pub in replica_pubs.items()
        }

    def valid(self, replica_id: int, msg: bytes, signature: bytes) -> bool:
        from cryptography.exceptions import InvalidSignature

        key = self._keys.get(replica_id)
        if key is None or len(signature) != 64:
            return False
        try:
            key.verify(signature, hashlib.sha256(msg).digest())
        except InvalidSignature:
            return False
        return True


def make(replica_pubs: dict):
    return ReplyVerifier(replica_pubs).valid
