"""fabric_token_sdk_tpu — a TPU-native privacy-preserving token framework.

Capability-parity re-design of hyperledger-labs/fabric-token-sdk:
UTXO tokens with plaintext (`fabtoken`) and zero-knowledge (`zkatdlog`)
drivers, token transaction services, and a batched JAX/XLA compute path
for the elliptic-curve / pairing cryptography hot loop.

Layers (see SURVEY.md):
  ops/       TPU limb-tensor bigint, fields, curves, pairing, multiexp
  crypto/    ZK protocol layer (pedersen, schnorr, pssign, range, ...)
  models/    token data model (Token, ID, Quantity, actions, request)
  api/       token management service facade (TMS, wallets, validator)
  drivers/   fabtoken + zkatdlog driver implementations
  services/  ttx, vault, selector, ttxdb, auditor, network, ...
  utils/     serialization, hashing, tracing, errors
"""

__version__ = "0.5.0"
