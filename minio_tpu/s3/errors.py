"""S3 API error catalogue + XML rendering (cmd/api-errors.go, ~300 codes in
the reference; here the subset the implemented APIs can produce, extended as
handlers land).
"""

from __future__ import annotations

import xml.etree.ElementTree as ET
from dataclasses import dataclass

from ..objectlayer import interface as ol


@dataclass(frozen=True)
class APIError:
    code: str
    description: str
    http_status: int


_ERRORS = {
    "AccessDenied": APIError("AccessDenied", "Access Denied.", 403),
    "BadDigest": APIError(
        "BadDigest", "The Content-Md5 you specified did not match what we "
        "received.", 400),
    "BucketAlreadyExists": APIError(
        "BucketAlreadyExists", "The requested bucket name is not "
        "available. The bucket namespace is shared by all users of the "
        "system.", 409),
    "BucketAlreadyOwnedByYou": APIError(
        "BucketAlreadyOwnedByYou",
        "Your previous request to create the named bucket succeeded and you "
        "already own it.", 409),
    "BucketNotEmpty": APIError(
        "BucketNotEmpty", "The bucket you tried to delete is not empty.",
        409),
    "EntityTooLarge": APIError(
        "EntityTooLarge", "Your proposed upload exceeds the maximum allowed "
        "object size.", 400),
    "ExpiredToken": APIError(
        "ExpiredToken", "The provided token has expired.", 400),
    "IncompleteBody": APIError(
        "IncompleteBody", "You did not provide the number of bytes "
        "specified by the Content-Length HTTP header.", 400),
    "InternalError": APIError(
        "InternalError", "We encountered an internal error, please try "
        "again.", 500),
    "InvalidAccessKeyId": APIError(
        "InvalidAccessKeyId", "The Access Key Id you provided does not "
        "exist in our records.", 403),
    "InvalidArgument": APIError(
        "InvalidArgument", "Invalid Argument", 400),
    "InvalidBucketName": APIError(
        "InvalidBucketName", "The specified bucket is not valid.", 400),
    "InvalidDigest": APIError(
        "InvalidDigest", "The Content-Md5 you specified is not valid.", 400),
    "InvalidPart": APIError(
        "InvalidPart", "One or more of the specified parts could not be "
        "found.", 400),
    "InvalidPartOrder": APIError(
        "InvalidPartOrder", "The list of parts was not in ascending order.",
        400),
    "InvalidRange": APIError(
        "InvalidRange", "The requested range is not satisfiable", 416),
    "InvalidRequest": APIError("InvalidRequest", "Invalid Request", 400),
    "MalformedXML": APIError(
        "MalformedXML", "The XML you provided was not well-formed or did "
        "not validate against our published schema.", 400),
    "MethodNotAllowed": APIError(
        "MethodNotAllowed", "The specified method is not allowed against "
        "this resource.", 405),
    "MissingContentLength": APIError(
        "MissingContentLength", "You must provide the Content-Length HTTP "
        "header.", 411),
    "NoSuchBucket": APIError(
        "NoSuchBucket", "The specified bucket does not exist", 404),
    "NoSuchKey": APIError(
        "NoSuchKey", "The specified key does not exist.", 404),
    "NoSuchUpload": APIError(
        "NoSuchUpload", "The specified multipart upload does not exist. "
        "The upload ID may be invalid, or the upload may have been aborted "
        "or completed.", 404),
    "NoSuchVersion": APIError(
        "NoSuchVersion", "The specified version does not exist.", 404),
    "InvalidStorageClass": APIError(
        "InvalidStorageClass", "The storage class you specified is not "
        "valid", 400),
    "InvalidObjectState": APIError(
        "InvalidObjectState", "The operation is not valid for the "
        "object's storage class", 403),
    "NotImplemented": APIError(
        "NotImplemented", "A header you provided implies functionality "
        "that is not implemented", 501),
    "PreconditionFailed": APIError(
        "PreconditionFailed", "At least one of the pre-conditions you "
        "specified did not hold", 412),
    "RequestTimeTooSkewed": APIError(
        "RequestTimeTooSkewed", "The difference between the request time "
        "and the server's time is too large.", 403),
    "SignatureDoesNotMatch": APIError(
        "SignatureDoesNotMatch", "The request signature we calculated does "
        "not match the signature you provided. Check your key and signing "
        "method.", 403),
    "AuthorizationHeaderMalformed": APIError(
        "AuthorizationHeaderMalformed",
        "The authorization header is malformed.", 400),
    "AuthorizationQueryParametersError": APIError(
        "AuthorizationQueryParametersError",
        "Error parsing the X-Amz-Credential parameter.", 400),
    "SlowDown": APIError(
        "SlowDown", "Resource requested is unreadable, please reduce your "
        "request rate", 503),
    "RequestTimeout": APIError(
        "RequestTimeout", "Your socket connection to the server was not "
        "read from or written to within the timeout period.", 408),
    "XMinioServerNotInitialized": APIError(
        "XMinioServerNotInitialized", "Server not initialized yet, please "
        "try again.", 503),
    "NoSuchBucketPolicy": APIError(
        "NoSuchBucketPolicy", "The bucket policy does not exist", 404),
    "NoSuchLifecycleConfiguration": APIError(
        "NoSuchLifecycleConfiguration",
        "The lifecycle configuration does not exist", 404),
    "ReplicationConfigurationNotFoundError": APIError(
        "ReplicationConfigurationNotFoundError",
        "The replication configuration was not found", 404),
    "ServerSideEncryptionConfigurationNotFoundError": APIError(
        "ServerSideEncryptionConfigurationNotFoundError",
        "The server side encryption configuration was not found", 404),
    "ObjectLockConfigurationNotFoundError": APIError(
        "ObjectLockConfigurationNotFoundError",
        "Object Lock configuration does not exist for this bucket", 404),
    "InvalidBucketObjectLockConfiguration": APIError(
        "InvalidBucketObjectLockConfiguration",
        "Bucket is missing ObjectLockConfiguration", 400),
    "NoSuchObjectLockConfiguration": APIError(
        "NoSuchObjectLockConfiguration",
        "The specified object does not have an ObjectLock configuration",
        404),
    "ObjectLocked": APIError(
        "ObjectLocked", "Object is WORM protected and cannot be "
        "overwritten or deleted", 400),
    "NoSuchTagSet": APIError(
        "NoSuchTagSet", "The TagSet does not exist", 404),
    "InvalidTag": APIError(
        "InvalidTag", "The tag provided was not a valid tag. A provided "
        "tag key or value was invalid.", 400),
    "MalformedPolicy": APIError(
        "MalformedPolicy", "Policy has invalid resource.", 400),
    "NoSuchWebsiteConfiguration": APIError(
        "NoSuchWebsiteConfiguration",
        "The specified bucket does not have a website configuration", 404),
    "NoSuchCORSConfiguration": APIError(
        "NoSuchCORSConfiguration",
        "The CORS configuration does not exist", 404),
    "BadRequest": APIError("BadRequest", "400 BadRequest", 400),
    "InvalidBucketState": APIError(
        "InvalidBucketState", "The request is not valid with the current "
        "state of the bucket.", 409),
    "AdminBucketQuotaExceeded": APIError(
        "XMinioAdminBucketQuotaExceeded",
        "Bucket quota may be exceeded with this request.", 403),
    "ReplicationDestinationNotFoundError": APIError(
        "ReplicationDestinationNotFoundError",
        "The replication destination bucket does not exist", 404),
    # SSE (cmd/api-errors.go crypto section)
    "InvalidEncryptionAlgorithmError": APIError(
        "InvalidEncryptionAlgorithmError",
        "The Encryption request you specified is not valid. Supported "
        "value: AES256.", 400),
    "SSECustomerKeyMD5Mismatch": APIError(
        "InvalidArgument",
        "The calculated MD5 hash of the key did not match the hash that "
        "was provided.", 400),
    "SSEEncryptedObject": APIError(
        "InvalidRequest", "The object was stored using a form of Server "
        "Side Encryption. The correct parameters must be provided to "
        "retrieve the object.", 400),
    "InsecureSSECustomerRequest": APIError(
        "InvalidRequest", "Requests specifying Server Side Encryption "
        "with Customer provided keys must be made over a secure "
        "connection.", 400),
    "KMSNotConfigured": APIError(
        "KMSNotConfigured", "Server side encryption specified but KMS is "
        "not configured", 400),
    "InvalidCopySource": APIError(
        "InvalidArgument", "Copy Source must mention the source bucket "
        "and key: sourcebucket/sourcekey.", 400),
    "InvalidCopyDest": APIError(
        "InvalidRequest", "This copy request is illegal because it is "
        "trying to copy an object to itself without changing the "
        "object's metadata, storage class, website redirect location or "
        "encryption attributes.", 400),
    # S3 Select (cmd/api-errors.go select section)
    "ParseSelectFailure": APIError(
        "ParseSelectFailure", "The SQL expression contains an invalid "
        "token or is otherwise not parseable.", 400),
    "EvaluatorInvalidArguments": APIError(
        "EvaluatorInvalidArguments", "Incorrect number of arguments in "
        "the function call or invalid evaluation.", 400),
    "InvalidExpressionType": APIError(
        "InvalidExpressionType", "The ExpressionType is invalid. Only "
        "SQL expressions are supported.", 400),
    "InvalidDataSource": APIError(
        "InvalidDataSource", "Invalid data source type. Only CSV and "
        "JSON are supported.", 400),
    "InvalidCompressionFormat": APIError(
        "InvalidCompressionFormat", "The file is not in a supported "
        "compression format. Only GZIP is supported.", 400),
    "InvalidRequestParameter": APIError(
        "InvalidRequestParameter", "The value of a parameter in "
        "SelectRequest element is invalid.", 400),
    "CSVParsingError": APIError(
        "CSVParsingError", "Encountered an error parsing the CSV file. "
        "Check the file and try again.", 400),
    "JSONParsingError": APIError(
        "JSONParsingError", "Encountered an error parsing the JSON file. "
        "Check the file and try again.", 400),
    "MalformedPOSTRequest": APIError(
        "MalformedPOSTRequest", "The body of your POST request is not "
        "well-formed multipart/form-data.", 400),
    "EntityTooSmall": APIError(
        "EntityTooSmall", "Your proposed upload is smaller than the "
        "minimum allowed object size.", 400),
}


def get(code: str) -> APIError:
    return _ERRORS.get(code, _ERRORS["InternalError"])


def has(code: str) -> bool:
    return code in _ERRORS


def from_object_error(e: Exception) -> APIError:
    """Map object layer errors to S3 codes
    (toAPIErrorCode, cmd/api-errors.go)."""
    mapping = {
        ol.BucketNotFound: "NoSuchBucket",
        ol.BucketExists: "BucketAlreadyOwnedByYou",
        ol.BucketNotEmpty: "BucketNotEmpty",
        ol.BucketNameInvalid: "InvalidBucketName",
        ol.ObjectNotFound: "NoSuchKey",
        ol.VersionNotFound: "NoSuchVersion",
        ol.MethodNotAllowed: "MethodNotAllowed",
        ol.ObjectNameInvalid: "InvalidArgument",
        ol.InvalidRange: "InvalidRange",
        ol.ReadQuorumError: "SlowDown",
        ol.WriteQuorumError: "SlowDown",
        ol.InvalidUploadID: "NoSuchUpload",
        ol.InvalidPart: "InvalidPart",
        ol.InvalidPartOrder: "InvalidPartOrder",
        ol.PreconditionFailed: "PreconditionFailed",
    }
    return get(mapping.get(type(e), "InternalError"))


def to_xml(err: APIError, resource: str = "", request_id: str = "") -> bytes:
    root = ET.Element("Error")
    ET.SubElement(root, "Code").text = err.code
    ET.SubElement(root, "Message").text = err.description
    ET.SubElement(root, "Resource").text = resource
    ET.SubElement(root, "RequestId").text = request_id
    return (b'<?xml version="1.0" encoding="UTF-8"?>' +
            ET.tostring(root))
